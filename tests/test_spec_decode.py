"""SLO-customized speculative decoding over paged KV.

A spec_decode engine drafts tokens with a model-free n-gram /
prompt-lookup drafter, verifies the whole proposal in ONE forward pass
over the paged cache (`Model.spec_decode_block`), accepts the longest
matching prefix, and rolls back rejected lanes as page-table
truncation (`PagedKVManager.truncate`).  Greedy acceptance makes the
output stream *token-identical* to plain greedy decode — across page /
chunk sizes, mid-stream P/D export, and live migration of a
speculating request.  Per-lane speculation depth comes from the TPOT
slack of each request's SLO (Eq. 5 family), so tiers with tight TPOT
speculate shallower than loose ones.
"""

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.latency_model import (
    FittedLatencyModel,
    LatencyCoeffs,
    LatencyModel,
)
from repro.core.request import Request, RequestState
from repro.models import build_model
from repro.serving.engine import EngineConfig, InferenceEngine
from repro.serving.kv_manager import PagedKVManager
from repro.serving.prefix_cache import PrefixCache
from repro.serving.spec_decode import (
    NGramDrafter,
    SpecConfig,
    expected_emitted,
    slo_spec_len,
)
from repro.serving.worker import SimWorker

from _greedy_ref import greedy_reference

SMOKE = get_smoke_config("qwen7b")
_MODEL = build_model(SMOKE)
_PARAMS = _MODEL.init(jax.random.key(0))
_FN_CACHE: dict = {}   # shared jitted steps across every engine below


def _engine(decode_block=1, page_size=8, chunk_size=16, n_slots=4,
            max_len=48, model=_MODEL, params=_PARAMS,
            fn_cache=_FN_CACHE, **kw):
    return InferenceEngine(
        model, params,
        EngineConfig(n_slots=n_slots, max_len=max_len, prefill_batch=2,
                     page_size=page_size, chunk_size=chunk_size,
                     decode_block=decode_block, **kw),
        fn_cache=fn_cache,
    )


def _spec_engine(page_size=8, chunk_size=16, n_slots=4, max_len=48,
                 max_spec_len=4, **kw):
    return _engine(1, page_size, chunk_size, n_slots, max_len,
                   spec_decode=True, max_spec_len=max_spec_len, **kw)


def _rep_prompts():
    """Prompts with enough self-repetition for the drafter to fire
    (plus one fully random control)."""
    rng = np.random.default_rng(7)
    return [
        np.array([3, 5, 7, 11] * 3, np.int32),
        np.array([2, 4] * 5, np.int32),
        np.array([9] * 8, np.int32),
        rng.integers(0, SMOKE.vocab_size, size=9).astype(np.int32),
    ]


def _run(eng, prompts, max_new=10, **req_kw):
    reqs = [Request.from_prompt(i, p, max_new=max_new, **req_kw)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.finish_time is not None for r in reqs)
    return reqs


# ---------------------------------------------------------------------------
# Drafter: deterministic, longest-n-gram + latest-occurrence preference
# ---------------------------------------------------------------------------

def test_drafter_deterministic_latest_occurrence():
    d = NGramDrafter(max_ngram=3, min_ngram=1)
    h = [1, 2, 3, 9, 1, 2, 3, 7, 1, 2, 3]
    # the trailing 3-gram (1,2,3) occurs at 0 and 4; the LATEST match
    # wins, so the continuation comes from position 4+3
    assert d.propose(h, 3) == [7, 1, 2]
    # deterministic: same history -> same proposal, every call
    for _ in range(3):
        assert d.propose(list(h), 3) == [7, 1, 2]
    # k truncates the continuation
    assert d.propose(h, 1) == [7]
    assert d.propose(h, 0) == []


def test_drafter_prefers_longer_ngram():
    d = NGramDrafter(max_ngram=3, min_ngram=1)
    # trailing 2-gram (4,5) matches at 0 -> continuation 9; a 1-gram
    # match on 5 alone (latest at index 3 -> continuation 4) must lose
    assert d.propose([4, 5, 9, 5, 4, 5], 1) == [9]


def test_drafter_no_match_and_degenerate_histories():
    d = NGramDrafter()
    assert d.propose([], 4) == []
    assert d.propose([1], 4) == []
    assert d.propose([5, 6, 7, 8], 4) == []  # no repeated n-gram


# ---------------------------------------------------------------------------
# SLO controller: depth from TPOT slack (Eq. 5 family)
# ---------------------------------------------------------------------------

def test_slo_spec_len_controller():
    cfg = SpecConfig(max_spec_len=8, unfitted_default=2)
    # unfitted profiler: conservative fixed default
    assert slo_spec_len(0.5, FittedLatencyModel(), [10], cfg) == 2
    # fitted: K = slack / b, floored, clamped to [0, max_spec_len]
    # (binary-exact coeffs so int() truncation is deterministic)
    m = LatencyModel(LatencyCoeffs(a=0.0, b=0.5, c=0.0,
                                   a_d=1.0, b_d=0.0, c_d=0.0))
    assert slo_spec_len(2.0, m, [10], cfg) == 2    # slack 1.0 / b 0.5
    assert slo_spec_len(1.5, m, [10], cfg) == 1
    assert slo_spec_len(0.5, m, [10], cfg) == 0    # no slack at all
    assert slo_spec_len(100.0, m, [10], cfg) == 8  # clamped at max
    # monotone: looser TPOT never speculates shallower
    ks = [slo_spec_len(t, m, [10], cfg) for t in (1.0, 1.5, 2.5, 4.0)]
    assert ks == sorted(ks)


def test_expected_emitted_and_spec_step_time():
    assert expected_emitted(0, 0.7) == 1.0
    assert expected_emitted(4, 0.0) == 1.0
    assert expected_emitted(3, 1.0) == pytest.approx(4.0)
    # geometric acceptance: 1 + a + a^2 for k=2
    assert expected_emitted(2, 0.5) == pytest.approx(1.75)
    m = LatencyModel(LatencyCoeffs(0.0, 0.5, 0.0, 1.0, 0.0, 0.0))
    # verify lanes priced at the prefill per-token rate
    assert m.spec_step_time([10], 4) == pytest.approx(3.0)
    assert m.spec_step_time([10], 0) == pytest.approx(
        m.decode_step_time([10]))


# ---------------------------------------------------------------------------
# Satellite: decode-block profiler attributes wall time to accepted
# tokens only (trailing rejected lanes trimmed)
# ---------------------------------------------------------------------------

def test_observe_decode_block_trims_trailing_empty_iterations():
    m = FittedLatencyModel()
    # 4 lanes dispatched, last 2 fully rejected: wall time divides over
    # the 2 iterations that emitted, not 4
    m.observe_decode_block([[10, 12], [11], [], []], 0.4)
    assert len(m._d_samples) == 2
    assert all(t == pytest.approx(0.2) for _, _, t in m._d_samples)
    # fully-rejected dispatch contributes nothing
    m2 = FittedLatencyModel()
    m2.observe_decode_block([[], [], []], 1.0)
    assert not m2._d_samples
    # interior empties still absorb their share (engine overhead) but
    # carry no sample — only TRAILING empties are trimmed
    m3 = FittedLatencyModel()
    m3.observe_decode_block([[5], [], [7], []], 0.3)
    assert len(m3._d_samples) == 2
    assert all(t == pytest.approx(0.1) for _, _, t in m3._d_samples)


# ---------------------------------------------------------------------------
# Rollback-as-truncation: PagedKVManager invariants
# ---------------------------------------------------------------------------

def test_truncate_basic():
    kv = PagedKVManager(n_slots=2, max_len=32, page_size=4)
    assert kv.ensure(0, 14)              # 4 pages
    assert kv.truncate(0, 9) == 1        # 3 pages cover 9 tokens
    assert kv.n_pages_held(0) == 3
    assert kv.truncate(0, 9) == 0        # idempotent
    assert kv.truncate(0, 12) == 0       # same page count: no-op
    assert kv.truncate(0, 0) == 3        # full rollback
    assert kv.pages_of(0) == []
    assert (kv.table[0] == -1).all()
    assert kv.n_free_pages == kv.n_pages


def test_truncate_invalidates_device_table():
    kv = PagedKVManager(n_slots=1, max_len=32, page_size=4)
    kv.ensure(0, 12)
    t0 = kv.device_table()
    kv.truncate(0, 12)                   # no-op: same buffer
    assert kv.device_table() is t0
    kv.truncate(0, 4)                    # shrinks: re-upload
    t1 = kv.device_table()
    assert t1 is not t0
    assert np.array_equal(np.asarray(t1), kv.table)


# ---------------------------------------------------------------------------
# Rollback over a shared cached prefix: refcounts stay exact
# (the hypothesis generalization of this lives in test_properties.py)
# ---------------------------------------------------------------------------

def test_truncate_prefix_refcounts():
    """A slot speculating on top of a shared cached prefix: rollback
    must deref shared pages through the cache (never hand a pinned
    page to the allocator) and keep every refcount exact."""
    steps = [(4, 2), (6, 0), (1, 1), (5, 5), (3, 0), (6, 4)]
    kv = PagedKVManager(n_slots=2, max_len=256, page_size=4)
    pc = PrefixCache(kv.alloc, 4)
    kv.attach_prefix_cache(pc)

    toks = list(range(13))
    assert kv.ensure(0, len(toks))
    assert kv.publish_prefix(0, toks) == 3     # 3 full pages cached

    hit = kv.lookup_prefix(1, toks + [50, 51, 52])
    assert hit == 12
    shared = kv.pages_of(1)
    assert len(shared) == 3
    pos = hit + 1                               # first private token
    assert kv.ensure(1, pos)

    for k, acc in steps:
        acc = min(acc, k)
        if pos + k + 1 > 256:
            break
        # speculate: grow to cover the proposal, then roll back to the
        # accepted prefix — an arbitrary accept/reject outcome
        assert kv.ensure(1, pos + k + 1)
        pos += acc + 1
        kv.truncate(1, pos)
        assert kv.n_pages_held(1) == -(-pos // 4)
        # shared span never truncated (engine floor: resident pos)
        assert kv.pages_of(1)[:3] == shared
        for p in shared:
            assert pc.refs(p) == 2              # publisher + this slot
        # conservation incl. the shared pages counted once
        held = kv.n_pages_held(0) + kv.n_pages_held(1) - len(shared)
        assert kv.alloc.n_used == held
        assert pc.n_reclaimable == 0            # everything pinned

    kv.release(1)
    for p in shared:
        assert pc.refs(p) == 1                  # publisher still holds
    kv.release(0)
    assert pc.n_reclaimable == 3                # unpinned, resident
    assert pc.evict(3) == 3
    assert kv.n_free_pages == kv.n_pages


# ---------------------------------------------------------------------------
# Token identity: --spec-decode and plain decode vs the cache-free
# greedy reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("page_size,chunk_size", [(4, 8), (8, 16)])
def test_spec_token_identical_to_plain(page_size, chunk_size):
    want = greedy_reference(_MODEL, _PARAMS, _rep_prompts(), 12)
    base = _run(_engine(1, page_size, chunk_size), _rep_prompts(),
                max_new=12)
    assert [r.generated for r in base] == want
    eng = _spec_engine(page_size, chunk_size)
    spec = _run(eng, _rep_prompts(), max_new=12)
    assert [r.generated for r in spec] == want
    # speculation actually fired (repetitive prompts guarantee
    # proposals on the first decode steps) and accounting balances
    assert eng.n_spec_dispatches > 0
    assert eng.n_spec_proposed >= eng.n_spec_accepted >= 0
    assert eng.kv.n_free_pages == eng.kv.n_pages


def test_spec_identical_to_fixed_k_blocks():
    """Same stream whether the engine runs fused K-blocks or
    propose-verify dispatches — both are greedy."""
    blk = _run(_engine(8), _rep_prompts(), max_new=12)
    spec = _run(_spec_engine(), _rep_prompts(), max_new=12)
    assert [r.generated for r in spec] == [r.generated for r in blk]


def test_spec_eos_stops_identically():
    (tokens,) = greedy_reference(_MODEL, _PARAMS, _rep_prompts()[:1], 12)
    eos = tokens[5]
    want = tokens[: tokens.index(eos) + 1]
    eng = _spec_engine(n_slots=1, eos_token=int(eos))
    (r,) = _run(eng, _rep_prompts()[:1], max_new=12)
    assert r.generated == want
    assert eng.kv.n_free_pages == eng.kv.n_pages


def test_spec_depth_follows_tpot_slack():
    """With a FITTED profiler (the Appendix-A estimator, not bare
    coefficients), a tight-TPOT tier speculates shallower than a loose
    one — the per-tier depth split BENCH_spec measures end-to-end."""
    truth = LatencyModel(LatencyCoeffs(0.0, 0.01, 0.0, 0.05, 0.0, 0.0))
    prof = FittedLatencyModel()
    for lens in ([4], [8], [4, 8], [16], [8, 16], [32], [4, 32], [64]):
        prof.observe_prefill(lens, truth.prefill_time(lens))
        prof.observe_decode(lens, truth.decode_step_time(lens))
    assert prof.fit()
    cfg = SpecConfig(max_spec_len=8)
    e_d = prof.decode_step_time([24])
    tight = slo_spec_len(e_d + 2.0 * prof.b, prof, [24], cfg)
    loose = slo_spec_len(e_d + 100.0 * prof.b, prof, [24], cfg)
    assert 1 <= tight <= 2
    assert loose == cfg.max_spec_len
    assert tight < loose
    assert slo_spec_len(e_d * 0.5, prof, [24], cfg) == 0


# ---------------------------------------------------------------------------
# Mid-stream P/D export + live migration of a speculating request
# ---------------------------------------------------------------------------

def test_spec_pd_export_and_migration_identity():
    """Park on a spec prefill engine, migrate, SPECULATE on the
    destination (different page size), export mid-stream, finish on a
    per-token engine — token-identical to the unmigrated plain run."""
    prompt = _rep_prompts()[0]
    base = _run(_engine(1, n_slots=1, max_len=64), [prompt.copy()],
                max_new=16)
    want = base[0].generated

    a = _spec_engine(n_slots=1, max_len=64)
    a.park_on_prefill = True
    r = Request.from_prompt(0, prompt.copy(), max_new=16)
    a.submit(r)
    a.run_until_done()
    assert r.slot in a.parked
    pay = a.export_kv(r.rid)
    a.evict(r.slot)

    b = _spec_engine(n_slots=1, max_len=64, page_size=4)
    assert b.import_kv(pay, r)
    while len(r.generated) < 15:
        b.step()
    # the output stream develops repeats, so the drafter fired and at
    # least one proposal survived verification before the export
    assert b.n_spec_dispatches > 0
    assert b.n_spec_accepted > 0
    assert r.generated == want[: len(r.generated)]
    # host pos stays exact through accept/rollback: the payload covers
    # exactly the accepted tokens
    pay2 = b.export_kv(r.rid)
    assert pay2.n_tokens == int(b.pos[r.slot])
    b.evict(r.slot)
    assert b.kv.n_free_pages == b.kv.n_pages

    c = _engine(1, n_slots=1, max_len=64)
    assert c.import_kv(pay2, r)
    c.run_until_done()
    assert r.generated == want
    assert r.state == RequestState.FINISHED


# ---------------------------------------------------------------------------
# Refusals + warm buckets
# ---------------------------------------------------------------------------

def test_spec_decode_refuses_slot_plane():
    with pytest.raises(ValueError, match="paged"):
        _spec_engine(paged=False)


def test_spec_decode_refuses_ssm_architectures():
    cfg = get_smoke_config("mamba2-2.7b")
    model = build_model(cfg)
    assert not model.supports_spec_decode
    assert _MODEL.supports_spec_decode
    params = model.init(jax.random.key(0))
    with pytest.raises(ValueError, match="spec_decode"):
        _engine(1, model=model, params=params, fn_cache={},
                spec_decode=True)


def test_warm_decode_blocks_covers_spec_buckets():
    eng = _spec_engine(max_spec_len=4)
    eng.warm_decode_blocks()
    # pow2 verify-width buckets up to max_spec_len precompiled
    assert {1, 2, 4} <= set(eng._spec_fns)


# ---------------------------------------------------------------------------
# Sim plane mirrors acceptance-rate-scaled decode ticks
# ---------------------------------------------------------------------------

def _sim_worker(accept_rate):
    truth = LatencyModel(LatencyCoeffs(0.0, 0.5, 0.0, 1.0, 0.0, 0.0))
    return SimWorker(0, "collocated", truth, 10**9,
                     np.random.default_rng(0), noise=0.0,
                     spec_decode=True, max_spec_len=4,
                     spec_accept_rate=accept_rate)


def _sim_drain(w, r):
    now, steps = 0.0, []
    w.submit([r], now)
    while r.state != RequestState.FINISHED:
        out = w.run_step(now)
        assert out is not None
        now += out.duration
        w.finish_step(out, now)
        steps.append((out.kind, out.duration))
    return steps


def test_sim_worker_spec_mirror():
    # tpot_slo 2.0 against e_d=1.0, b=0.5 -> the controller plans k=2;
    # full acceptance emits 3 tokens per dispatch
    r = Request(rid=0, l_in=4, l_out=10, tpot_slo=2.0)
    w = _sim_worker(1.0)
    steps = _sim_drain(w, r)
    decs = [d for kind, d in steps if kind == "decode"]
    assert len(decs) == 3                       # 9 decode tokens / 3
    assert all(d == pytest.approx(2.0) for d in decs)  # 1.0 + 0.5*2
    assert w.spec_dispatches == 3
    assert w.spec_proposed == 6
    assert w.spec_accepted == 6

    # zero acceptance degenerates to one token per step — never fewer
    r0 = Request(rid=1, l_in=4, l_out=10, tpot_slo=2.0)
    w0 = _sim_worker(0.0)
    steps0 = _sim_drain(w0, r0)
    assert len([1 for kind, _ in steps0 if kind == "decode"]) == 9
    assert w0.spec_accepted == 0
    assert r0.tokens_done == 10
