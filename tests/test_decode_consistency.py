"""Incremental decode must reproduce the full forward pass — validates
KV caches, local-window ring buffers, SSD state carry, and shared-block
caches for every architecture family."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model

FAMS = ["qwen2.5-14b", "gemma3-4b", "mamba2-2.7b", "zamba2-7b",
        "olmoe-1b-7b", "phi3.5-moe-42b-a6.6b", "chameleon-34b",
        "command-r-plus-104b"]


@pytest.mark.parametrize("name", FAMS)
def test_decode_matches_forward(name):
    cfg = get_smoke_config(name)
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    b, s, t = 2, 20, 6
    toks = jax.random.randint(jax.random.key(2), (b, s + t), 0,
                              cfg.vocab_size)
    full = model.forward(params, {"tokens": toks})
    lens = jnp.full((b,), s, jnp.int32)
    lp, caches = model.prefill(params, toks[:, :s], lens, cache_len=s + t)
    errs = [float(jnp.max(jnp.abs(lp - full[:, s - 1])))]
    for i in range(t):
        lg, caches = model.decode_step(
            params, caches, toks[:, s + i], lens + i
        )
        errs.append(float(jnp.max(jnp.abs(lg - full[:, s + i]))))
    assert max(errs) < 1e-4, errs


CHUNK_FAMS = ["qwen2.5-14b", "mamba2-2.7b", "zamba2-7b", "olmoe-1b-7b",
              "phi3.5-moe-42b-a6.6b"]


@pytest.mark.parametrize("name", CHUNK_FAMS)
@pytest.mark.parametrize("chunk", [3, 8])
def test_chunked_prefill_matches_monolithic_logits(name, chunk):
    """Model-level: prefilling in chunks through the paged plane must
    reproduce monolithic prefill's last-token logits, then decode
    identically, for every chunk-capable family (dense / SSM / grouped
    shared-attn / MoE)."""
    cfg = get_smoke_config(name)
    model = build_model(cfg)
    assert model.supports_chunked
    params = model.init(jax.random.key(1))
    b, s, max_len, ps = 2, 13, 32, 4
    toks = jax.random.randint(jax.random.key(4), (b, s), 0, cfg.vocab_size)
    lens = jnp.full((b,), s, jnp.int32)
    want, _ = model.prefill(params, toks, lens, cache_len=max_len)

    from repro.serving.kv_manager import PagedKVManager
    kv = PagedKVManager(b, max_len, ps)
    for i in range(b):
        assert kv.ensure(i, s)
    caches = model.init_paged_cache(b, max_len, ps, kv.n_pages)
    pt = jnp.asarray(kv.table)
    logits = None
    for start in range(0, s, chunk):
        c = min(chunk, s - start)
        tk = jnp.zeros((b, chunk), jnp.int32)
        tk = tk.at[:, :c].set(toks[:, start: start + c])
        logits, caches, *_ = model.chunk_step(
            params, caches, pt, tk,
            jnp.full((b,), start, jnp.int32),
            jnp.full((b,), c, jnp.int32),
        )
    assert float(jnp.max(jnp.abs(logits - want))) < 1e-4


def test_engine_chunked_tokens_identical_to_monolithic():
    """Engine-level: the chunked/paged plane must generate
    token-for-token what the monolithic slot plane generates, for every
    tested chunk size — and reclaim every page."""
    from repro.core.request import Request
    from repro.serving.engine import EngineConfig, InferenceEngine
    import numpy as np

    cfg = get_smoke_config("qwen7b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 21, 11, 3)]

    def run(paged, chunk):
        reqs = [Request.from_prompt(i, p, max_new=4)
                for i, p in enumerate(prompts)]
        eng = InferenceEngine(model, params, EngineConfig(
            n_slots=2, max_len=48, prefill_batch=2, paged=paged,
            chunk_size=chunk, page_size=4))
        for r in reqs:
            eng.submit(r)
        eng.run_until_done()
        assert all(r.finish_time is not None for r in reqs)
        if paged:
            assert eng.kv.n_free_pages == eng.kv.n_pages
        return [r.generated for r in reqs]

    base = run(paged=False, chunk=32)
    for chunk in (5, 32):
        assert run(paged=True, chunk=chunk) == base, chunk


def test_ragged_prefill_lengths():
    """Per-sequence lens: padding rows must not leak into attention."""
    cfg = get_smoke_config("qwen2.5-14b")
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    toks = jax.random.randint(jax.random.key(3), (2, 16), 0,
                              cfg.vocab_size)
    lens = jnp.array([16, 9], jnp.int32)
    lp, _ = model.prefill(params, toks, lens)
    # row 1's last-token logits must equal an unpadded 9-token prefill
    lp_short, _ = model.prefill(
        params, toks[1:2, :9], jnp.array([9], jnp.int32)
    )
    assert float(jnp.max(jnp.abs(lp[1] - lp_short[0]))) < 1e-4
