"""Fused multi-token decode blocks (device-resident decode).

Every decode dispatch runs K >= 1 greedy iterations in one jitted
program (`Model.decode_block` / `decode_block_slots` — a lax.scan with
on-device EOS / max-len / l_out stopping).  At K=1 and K=8 the engine
must be *token-identical* to a cache-free greedy reference
(`Model.forward` over prompt + generated tokens) on both execution
planes, and K=8 to K=1 through preemption, P/D export of a
partially-consumed stream, and EOS stopping mid-block; profiler
attribution stays per-iteration so the Eq. 2 fit is unchanged, and
the warm-up leaves no decode program to compile in service.
"""

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.request import Request, RequestState
from repro.models import build_model
from repro.serving.engine import EngineConfig, InferenceEngine

from _greedy_ref import greedy_reference

SMOKE = get_smoke_config("qwen7b")
_MODEL = build_model(SMOKE)
_PARAMS = _MODEL.init(jax.random.key(0))
_FN_CACHE: dict = {}   # shared jitted steps across every engine below


def _engine(decode_block, page_size=8, chunk_size=16, n_slots=2,
            max_len=48, model=_MODEL, params=_PARAMS, fn_cache=_FN_CACHE,
            **kw):
    return InferenceEngine(
        model, params,
        EngineConfig(n_slots=n_slots, max_len=max_len, prefill_batch=2,
                     page_size=page_size, chunk_size=chunk_size,
                     decode_block=decode_block, **kw),
        fn_cache=fn_cache,
    )


def _prompts(n=4, sizes=(5, 21, 11, 3)):
    rng = np.random.default_rng(7)
    return [rng.integers(0, SMOKE.vocab_size, size=s).astype(np.int32)
            for s in sizes[:n]]


def _run(eng, prompts, max_new=10):
    reqs = [Request.from_prompt(i, p, max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.finish_time is not None for r in reqs)
    return reqs


# ---------------------------------------------------------------------------
# Token identity vs the cache-free reference, both planes, multiple
# chunk/page sizes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("page_size,chunk_size", [(4, 8), (8, 16)])
def test_paged_blocks_token_identical_to_per_token(page_size, chunk_size):
    want = greedy_reference(_MODEL, _PARAMS, _prompts(), 10)
    for k in (1, 8):
        got = _run(_engine(k, page_size, chunk_size), _prompts())
        assert [r.generated for r in got] == want, k
    # blocks actually ran fused (pure-decode phases exist with 2 slots)
    eng = _engine(8, page_size, chunk_size)
    reqs = _run(eng, _prompts(2, (5, 7)), max_new=12)
    assert any(k > 1 for k in eng.decode_block_hist), eng.decode_block_hist
    assert eng.kv.n_free_pages == eng.kv.n_pages
    assert all(len(r.generated) == 12 for r in reqs)


def test_slot_plane_blocks_token_identical():
    want = greedy_reference(_MODEL, _PARAMS, _prompts(), 10)
    for k in (1, 8):
        got = _run(_engine(k, paged=False), _prompts())
        assert [r.generated for r in got] == want, k


def test_mamba_blocks_token_identical():
    """SSM state carry through the fused scan (conv + SSD state ride
    the carry, frozen rows hold their state)."""
    cfg = get_smoke_config("mamba2-2.7b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    cache: dict = {}

    def run(k):
        eng = _engine(k, model=model, params=params, fn_cache=cache)
        return _run(eng, _prompts(2, (5, 9)), max_new=8)

    want = greedy_reference(model, params, _prompts(2, (5, 9)), 8)
    for k in (1, 8):
        assert [r.generated for r in run(k)] == want, k


# ---------------------------------------------------------------------------
# EOS stopping mid-block (partially-consumed block)
# ---------------------------------------------------------------------------

def test_eos_stops_mid_block():
    (tokens,) = greedy_reference(_MODEL, _PARAMS, _prompts(1, (9,)), 12)
    eos = tokens[5]
    stop = tokens.index(eos)  # first emission of the eos value
    want = tokens[: stop + 1]

    outs = {}
    for k in (1, 8):
        eng = _engine(k, n_slots=1, eos_token=int(eos))
        (r,) = _run(eng, _prompts(1, (9,)), max_new=12)
        outs[k] = r.generated
        assert r.generated[-1] == eos
        assert eng.kv.n_free_pages == eng.kv.n_pages
        if k == 8 and len(want) > 1:
            # the block overshoots the stream's end: lanes after EOS
            # come back invalid, and the finish stamp interpolates to
            # the emitting lane, strictly inside the block wall
            assert r.finish_time < eng.clock
    assert outs[8] == outs[1] == want


# ---------------------------------------------------------------------------
# Preemption under page pressure with blocks on
# ---------------------------------------------------------------------------

def test_preemption_under_page_pressure_with_blocks():
    """An oversubscribed pool shrinks K (page pre-reservation) and
    falls back to recompute preemption at K=1 — outputs stay
    token-exact vs a roomy pool."""
    prompts = _prompts(2, (10, 10))

    def run(decode_block, **kw):
        eng = _engine(decode_block, page_size=4, chunk_size=8,
                      max_len=16, **kw)
        reqs = _run(eng, [p.copy() for p in prompts], max_new=6)
        assert eng.kv.n_free_pages == eng.kv.n_pages
        return [r.generated for r in reqs]

    base = run(1)
    assert run(8) == base
    for n_pages in (4, 5):   # prefill- and decode-time preemption
        assert run(8, n_pages=n_pages) == base, n_pages


# ---------------------------------------------------------------------------
# P/D hand-off of a stream advanced by fused blocks
# ---------------------------------------------------------------------------

def test_pd_export_after_partial_blocks():
    """Host pos/last_token must stay exact through device-resident
    blocks: park on a prefill engine, decode with K=8 blocks on a
    second, export MID-STREAM, finish on a third (per-token, different
    page size) — token-identical to the unmigrated run."""
    base = _run(_engine(1, n_slots=1, max_len=64), _prompts(1, (12,)),
                max_new=16)
    want = base[0].generated

    a = _engine(8, n_slots=1, max_len=64)
    a.park_on_prefill = True
    r = Request.from_prompt(0, _prompts(1, (12,))[0], max_new=16)
    a.submit(r)
    a.run_until_done()
    assert r.slot in a.parked
    pay = a.export_kv(r.rid)
    a.evict(r.slot)

    b = _engine(8, n_slots=1, max_len=64)
    assert b.import_kv(pay, r)
    assert b._slot_of(r.rid) == r.slot
    while len(r.generated) < 9:   # a couple of fused blocks
        b.step()
    assert any(k > 1 for k in b.decode_block_hist), b.decode_block_hist
    assert r.generated == want[: len(r.generated)]
    pay2 = b.export_kv(r.rid)
    assert pay2.n_tokens == int(b.pos[r.slot])
    b.evict(r.slot)
    assert b.kv.n_free_pages == b.kv.n_pages

    c = _engine(1, n_slots=1, max_len=64, page_size=4)
    assert c.import_kv(pay2, r)
    c.run_until_done()
    assert r.generated == want
    assert r.state == RequestState.FINISHED


# ---------------------------------------------------------------------------
# Profiler: per-iteration attribution inside a block
# ---------------------------------------------------------------------------

def test_profiler_per_iteration_attribution():
    """A K-block contributes K Eq. 2 samples of wall/K each at the
    interpolated lengths — same sample stream per-token stepping
    produces, so the Appendix-A fit is block-size independent."""
    eng = _engine(4, n_slots=1)
    (r,) = _run(eng, _prompts(1, (8,)), max_new=9)
    samples = eng.profiler._d_samples
    # 8 decode tokens (first came from prefill) -> 8 samples, batch 1
    assert len(samples) == 8
    assert all(b == 1.0 for _, b, _ in samples)
    # lengths advance by one per iteration, across block boundaries
    lens = [s for s, _, _ in samples]
    assert lens == [lens[0] + i for i in range(8)]
    # two blocks of 4 -> times equal within each block
    assert eng.decode_block_hist.get(4) == 2
    t = [x for _, _, x in samples]
    assert t[0] == t[1] == t[2] == t[3] and t[4] == t[5] == t[6] == t[7]


# ---------------------------------------------------------------------------
# Satellites: rid->slot index, device-resident page table
# ---------------------------------------------------------------------------

def test_rid_slot_index_tracks_lifecycle():
    eng = _engine(8)
    reqs = _run(eng, _prompts(), max_new=6)
    assert eng._rid_slot == {}          # all retired
    assert eng._slot_of(reqs[0].rid) is None
    assert eng.kv_bytes_of(reqs[0].rid) is None

    a = _engine(8, n_slots=1)
    a.park_on_prefill = True
    r = Request.from_prompt(9, _prompts(1, (6,))[0], max_new=4)
    a.submit(r)
    a.run_until_done()
    assert a._slot_of(9) == r.slot      # parked: index live, O(1)
    assert a.kv_bytes_of(9) == a.export_kv(9).nbytes
    a.evict(r.slot)
    assert a._rid_slot == {}


def test_device_table_reuploads_only_on_allocation_change():
    from repro.serving.kv_manager import PagedKVManager

    kv = PagedKVManager(n_slots=2, max_len=32, page_size=8)
    t0 = kv.device_table()
    assert t0 is kv.device_table()      # clean: same resident buffer
    assert kv.ensure(0, 9)              # grows -> dirty
    t1 = kv.device_table()
    assert t1 is not t0
    assert np.array_equal(np.asarray(t1), kv.table)
    assert kv.ensure(0, 9)              # no growth -> still clean
    assert kv.device_table() is t1
    kv.release(0)
    t2 = kv.device_table()
    assert t2 is not t1
    assert (np.asarray(t2) == -1).all()


# ---------------------------------------------------------------------------
# Warm-up: every decode program compiled once, on the served arguments
# ---------------------------------------------------------------------------

def _decode_programs(eng):
    """Each decode program the engine holds -> its compiled count."""
    return {(kind, k): fn._cache_size()
            for kind, fns in (("block", eng._block_fns),
                              ("spec", eng._spec_fns))
            for k, fn in fns.items()}


@pytest.mark.parametrize("plane", ["paged", "slots", "spec"])
def test_warm_decode_blocks_leaves_nothing_to_compile(plane):
    """After ``warm_decode_blocks`` no decode program compiles again
    and none is added, while lone requests enter every power-of-two K
    from fresh host state (l_out = K + 1) and from the previous
    block's state (l_out 16 walks 8, 4, 2, 1; 17 runs 8 then 8), two
    requests run K = 1 beside a prefill, and (spec) verify passes run
    in every proposal-width bucket."""
    kw = {"paged": {}, "slots": {"paged": False},
          "spec": {"spec_decode": True, "max_spec_len": 2}}[plane]
    eng = _engine(8, fn_cache={}, **kw)
    eng.warm_decode_blocks()
    warmed = _decode_programs(eng)
    assert all(n == 1 for n in warmed.values()), warmed
    prompt = np.array([3, 5, 7, 11] * 3, np.int32)  # the drafter fires
    for l_out in (17, 16, 9, 5, 3, 2):
        _run(eng, [prompt], max_new=l_out)
    _run(eng, _prompts(2, (5, 21)), max_new=6)
    assert _decode_programs(eng) == warmed
    if plane == "spec":
        assert set(eng.spec_depth_hist) == {1, 2}
    else:
        assert set(eng.decode_block_hist) == {1, 2, 4, 8}
