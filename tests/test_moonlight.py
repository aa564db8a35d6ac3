"""Latent attention (MLA) and the dropless held-expert layer, as the
DeepSeek-V3 block of Moonlight-16B-A3B runs them, at a small size on the
CPU against ``perfbench/reference/deepseek_v3.py``.

The configuration is the benchmark's own file with small widths: 3
layers (1 dense), 4 heads, a 32-wide latent, a router over 12 experts
that picks 3, of which this "chip" holds 4 (from expert 4 on), and 2
shared experts.  Weights come from the reference's ``init_weights``
and reach the program through ``perfbench/adapters/deepseek_v3.py``,
as in the benchmark.

Tolerances: the program and the reference are both float32 on the CPU;
they differ only in summation order (the absorbed against the expanded
attention, the experts' ragged dots against dense per-expert products),
some 1e-6 on logits of order 1, so 1e-4 holds with room and a wrong
rotation, scale, norm or routing weight moves logits by 1e-2 or more.
"""

import glob
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

BENCH = pathlib.Path(__file__).resolve().parents[1] / "perfbench"
sys.path.insert(0, str(BENCH))

from harness.cell import load_module  # noqa: E402

from repro.configs import get_smoke_config  # noqa: E402
from repro.core.request import Request  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models import moe  # noqa: E402
from repro.serving.engine import EngineConfig, InferenceEngine  # noqa: E402
from repro.serving.kv_manager import PagedKVManager  # noqa: E402

REF = load_module(BENCH / "reference" / "deepseek_v3.py")
ADAPTER = load_module(BENCH / "adapters" / "deepseek_v3.py")
TOL = 1e-4


def tiny_config(**over) -> dict:
    cfg = json.loads((BENCH / "configs" / "moonlight-16b-a3b.json")
                     .read_text())
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               kv_lora_rank=32, intermediate_size=128,
               moe_intermediate_size=32, vocab_size=256,
               num_hidden_layers=3, num_experts_per_tok=3,
               n_routed_experts=4, initializer_range=0.1,
               held_experts={"first": 4, "router_width": 12,
                             "expert_parallel": 3})
    cfg.update(over)
    return cfg


CFG = tiny_config()
WEIGHTS = REF.init_weights(CFG, jax.random.key(0))
MODEL = build_model(ADAPTER.model_config(CFG))
PARAMS = ADAPTER.program_params(WEIGHTS)
FN_CACHE: dict = {}


def ref_logits(tokens):
    tokens = jnp.asarray(tokens, jnp.int32)
    return REF.logits(WEIGHTS, REF.hidden(WEIGHTS, CFG, tokens))


def test_program_tree_is_the_adapters_layout():
    want = jax.tree.map(lambda a: a.shape, MODEL.abstract_params())
    assert jax.tree.map(lambda a: a.shape, PARAMS) == want
    assert MODEL.supports_chunked and MODEL.supports_prefix_cache
    assert MODEL.supports_spec_decode and MODEL.counts_experts


def test_forward_matches_reference():
    toks = jax.random.randint(jax.random.key(1), (20,), 0, 256)
    got = MODEL.forward(PARAMS, {"tokens": toks[None]})[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref_logits(toks)),
                               atol=TOL, rtol=0)


def test_chunked_prefill_then_decode_blocks_match_reference():
    """Prefill in three chunks, two fused decode blocks and a last
    one-token step through the latent pool: the prefill's and the last
    step's logits match the reference's full forward pass, and every
    token the blocks emit is the reference's greedy choice."""
    b, s, max_len, ps, chunk = 3, 13, 48, 4, 5
    toks = jax.random.randint(jax.random.key(4), (b, s), 0, 256)
    kv = PagedKVManager(b, max_len, ps)
    for i in range(b):
        assert kv.ensure(i, max_len)
    caches = MODEL.init_paged_cache(b, max_len, ps, kv.n_pages)
    pt = jnp.asarray(kv.table)
    for start in range(0, s, chunk):
        c = min(chunk, s - start)
        tk = jnp.zeros((b, chunk), jnp.int32).at[:, :c].set(
            toks[:, start:start + c])
        logits, caches, _ = MODEL.chunk_step(
            PARAMS, caches, pt, tk, jnp.full((b,), start, jnp.int32),
            jnp.full((b,), c, jnp.int32))
    for i in range(b):
        np.testing.assert_allclose(np.asarray(logits[i]),
                                   np.asarray(ref_logits(toks[i])[-1]),
                                   atol=TOL, rtol=0)
    seqs = np.concatenate([toks, np.argmax(logits, -1)[:, None]], 1)
    last = jnp.asarray(seqs[:, -1], jnp.int32)
    pos = jnp.full((b,), s, jnp.int32)
    alive = jnp.array([True, True, False])     # a lane left idle
    for _ in range(2):
        (out, valid, last, pos, load), caches = MODEL.decode_block(
            PARAMS, caches, pt, last, pos, alive, jnp.full((b,), 99),
            jnp.int32(-1), jnp.int32(max_len), k=4)
        assert bool(valid[:2].all()) and not bool(valid[2].any())
        assert 0 < int(load[0]) and 0 < int(load[1]) <= 4 * 2 * 4
        seqs = np.concatenate([seqs, np.asarray(out)], 1)
    logits, caches, _ = MODEL.chunk_step(PARAMS, caches, pt, last[:, None],
                                         pos, alive.astype(jnp.int32))
    for i in range(2):
        want = ref_logits(seqs[i])
        served = seqs[i, s:]
        rows = want[s - 1:-1]
        gap = rows.max(-1) - jnp.take_along_axis(
            rows, served[:, None], -1)[:, 0]
        assert float(gap.max()) < TOL, gap
        np.testing.assert_allclose(np.asarray(logits[i]),
                                   np.asarray(want[-1]), atol=TOL, rtol=0)


def test_held_shares_sum_to_the_uncut_layer():
    """Three chips of four experts each: the parts of the expert layer
    that the shares compute, with the shared experts (which every chip
    computes alike) counted once, add up to the whole layer of the
    reference that holds all twelve."""
    full = tiny_config(n_routed_experts=12,
                       held_experts={"first": 0, "router_width": 12})
    w = REF.init_weights(full, jax.random.key(3))["moe"]
    p = jax.tree.map(lambda a: a[0], w)
    n = REF.dims(full)
    h = jax.random.normal(jax.random.key(5), (2, 9, 64))
    want = jax.vmap(lambda x: REF.expert_ffn(x, p, n)[0])(h)
    shared = jax.vmap(lambda x: REF._swiglu(
        x, p["s_gate"], p["s_up"], p["s_down"], None))(h)
    total = shared
    for first in (0, 4, 8):
        share = tiny_config(held_experts={"first": first,
                                          "router_width": 12})
        mcfg = ADAPTER.model_config(share)
        params = {"w_router": p["router"], "router_bias": p["router_bias"],
                  "w_gate": p["e_gate"][first:first + 4],
                  "w_up": p["e_up"][first:first + 4],
                  "w_down": p["e_down"][first:first + 4],
                  "ws_gate": p["s_gate"], "ws_up": p["s_up"],
                  "ws_down": p["s_down"]}
        y, aux = moe.moe_ffn(params, h, mcfg)
        total = total + (y - shared)
        assert 0 < int(aux["experts_hit"]) <= 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=TOL, rtol=0)


@pytest.mark.parametrize("name", ["moonlight", "olmoe-1b-7b",
                                  "phi3.5-moe-42b-a6.6b"])
def test_lane_logits_do_not_depend_on_the_batch(name):
    """Dropless: the last lane's logits are the same alone and behind
    lanes that send every token to the same experts.  (A capacity of
    1.25 x the mean load per expert would keep the earlier lanes'
    assignments and drop the last lane's.)"""
    if name == "moonlight":
        # all twelve experts held, so that the crowd lands on held ones
        cfg = tiny_config(n_routed_experts=12,
                          held_experts={"first": 0, "router_width": 12})
        model = build_model(ADAPTER.model_config(cfg))
        params = ADAPTER.program_params(REF.init_weights(cfg,
                                                         jax.random.key(2)))
    else:
        model = build_model(get_smoke_config(name))
        params = model.init(jax.random.key(0))
    b, c, max_len, ps = 4, 12, 16, 4
    kv = PagedKVManager(b, max_len, ps)
    for i in range(b):
        assert kv.ensure(i, max_len)
    pt = jnp.asarray(kv.table)
    probe = jax.random.randint(jax.random.key(6), (c,), 0, 256)
    lens = jnp.full((b,), c, jnp.int32)
    start = jnp.zeros((b,), jnp.int32)

    def probe_logits(others, lens):
        tokens = jnp.concatenate([others, probe[None]], 0)
        caches = model.init_paged_cache(b, max_len, ps, kv.n_pages)
        return model.chunk_step(params, caches, pt, tokens, start,
                                lens)[0][-1]

    alone = probe_logits(jnp.zeros((b - 1, c), jnp.int32),
                         lens.at[:-1].set(0))
    crowded = probe_logits(jnp.full((b - 1, c), 7, jnp.int32), lens)
    np.testing.assert_allclose(np.asarray(crowded), np.asarray(alone),
                               atol=1e-6, rtol=0)


# -- the served path ---------------------------------------------------------


def _engine(**kw):
    cfg = dict(n_slots=4, max_len=48, prefill_batch=2, page_size=4,
               chunk_size=8, decode_block=4)
    cfg.update(kw)
    return InferenceEngine(MODEL, PARAMS, EngineConfig(**cfg),
                           fn_cache=FN_CACHE)


def _prompts():
    rng = np.random.default_rng(11)
    prefix = rng.integers(1, 256, size=9).astype(np.int32)
    return [np.concatenate([prefix, rng.integers(1, 256, size=3 + i)])
            .astype(np.int32) for i in range(3)] + [
        np.array([3, 5, 7, 11] * 3, np.int32)]


def _serve(eng, prompts, max_new=10):
    reqs = [Request.from_prompt(i, p.copy(), max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
        eng.run_until_done()   # one at a time: each publishes its prefix
    assert all(len(r.generated) == max_new for r in reqs)
    if eng.prefix is None:   # the prefix cache keeps its pages
        assert eng.kv.n_free_pages == eng.kv.n_pages
    return [r.generated for r in reqs]


@pytest.fixture(scope="module")
def collocated():
    return _serve(_engine(), _prompts())


def test_served_tokens_are_the_references(collocated):
    for p, gen in zip(_prompts(), collocated):
        seq = np.concatenate([p, gen])
        lg = ref_logits(seq[:-1])[len(p) - 1:]
        gap = lg.max(-1) - jnp.take_along_axis(
            lg, jnp.asarray(gen)[:, None], -1)[:, 0]
        assert float(gap.max()) < TOL


def test_pd_export_import_matches_collocated(collocated):
    """Prefill parks on one engine; the latent pages move to another
    engine (another page size) and decode there."""
    for i, p in enumerate(_prompts()):
        a = _engine()
        a.park_on_prefill = True
        r = Request.from_prompt(i, p.copy(), max_new=10)
        a.submit(r)
        a.run_until_done()
        payload = a.export_kv(r.rid)
        assert a.kv_bytes_of(r.rid) == payload.nbytes
        a.evict(r.slot)
        b = _engine(page_size=8)
        assert b.import_kv(payload, r)
        b.run_until_done()
        assert r.generated == collocated[i]


def test_migration_mid_decode_matches_collocated(collocated):
    p = _prompts()[1]
    a = _engine()
    r = Request.from_prompt(1, p.copy(), max_new=10)
    a.submit(r)
    while len(r.generated) < 4:
        a.step()
    payload = a.export_kv(r.rid)
    a.evict(r.slot)
    b = _engine()
    assert b.import_kv(payload, r)
    b.run_until_done()
    assert r.generated == collocated[1]


def test_prefix_cache_on_matches_off(collocated):
    eng = _engine(prefix_cache=True)
    assert _serve(eng, _prompts()) == collocated
    assert eng.prefix.stats()["n_hit_tokens"] > 0


def test_spec_decode_matches_plain(collocated):
    eng = _engine(spec_decode=True, max_spec_len=4, decode_block=1)
    assert _serve(eng, _prompts()) == collocated
    assert eng.n_spec_dispatches > 0


def test_steps_carry_the_held_experts_load(tmp_path):
    """Traced, every dispatching step's span carries ``expert_tokens``
    and ``experts_hit``: at most the held experts times the expert
    layers (times K for a fused block)."""
    eng = _engine()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(eng, _prompts()[:2], max_new=9)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    steps = [dict(e.stats) for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name == "hfx.engine.step"]
    steps = [s for s in steps if s.get("kind") in ("prefill_chunk", "decode")]
    assert {s["kind"] for s in steps} == {"prefill_chunk", "decode"}
    for s in steps:
        assert 0 < s["experts_hit"] <= 4 * 2 * s.get("k", 1), s
        assert s["experts_hit"] <= s["expert_tokens"], s
