"""Fast scaling on the engine plane (PR 6): per-replica weight
ownership via WeightManager, the three Table-2 provisioning transports
(d2d / cpu / disk) as real transfers, measured costs feeding the
TLManager model, and the Cluster's scale-out/scale-in commit paths."""

import dataclasses
import gc

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.request import Request
from repro.core.scaler import ScaleAction, ScalerConfig
from repro.core.tlmanager import TLManager
from repro.serving.cluster import Cluster, ClusterConfig
from repro.serving.engine import EngineConfig, InferenceEngine
from repro.serving.weights import STRATEGIES, WeightManager

SMOKE = get_smoke_config("qwen7b")


@pytest.fixture(scope="module")
def stack():
    from repro.models import build_model

    model = build_model(SMOKE)
    params = model.init(jax.random.key(0))
    return model, params


def _prompt(n=10):
    return (np.arange(1, n + 1, dtype=np.int32) * 3) % SMOKE.vocab_size


def _generate(model, params, fn_cache, max_new=5):
    eng = InferenceEngine(model, params, EngineConfig.smoke(),
                          fn_cache=fn_cache)
    r = Request.from_prompt(0, _prompt(), max_new=max_new)
    eng.submit(r)
    eng.run_until_done()
    return list(r.generated)


def _distinct_buffers(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    for x, y in zip(la, lb):
        if x is y:
            return False
        try:
            if x.unsafe_buffer_pointer() == y.unsafe_buffer_pointer():
                return False
        except (AttributeError, ValueError):
            pass
    return True


# ---------------------------------------------------------------------------
# WeightManager: ownership + the three transports
# ---------------------------------------------------------------------------

def test_provision_all_strategies_token_identical(stack):
    """Every Table-2 transport materializes a replica-owned tree whose
    buffers are distinct from the donor's AND whose engine generates
    exactly the seed replica's tokens."""
    model, params = stack
    wm = WeightManager(params, tl=TLManager())
    wm.adopt(0, params)
    fn_cache: dict = {}
    ref = _generate(model, params, fn_cache)
    assert ref  # the smoke model really decoded something
    for wid, strategy in enumerate(STRATEGIES, start=1):
        got, dt = wm.provision(
            wid, strategy, donor=0 if strategy == "d2d" else None
        )
        assert dt > 0.0
        assert wm.owns(wid)
        assert _distinct_buffers(params, got), strategy
        for x, y in zip(jax.tree.leaves(params), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert _generate(model, got, fn_cache) == ref, strategy


def test_adopt_and_release_track_ownership(stack):
    model, params = stack
    wm = WeightManager(params)
    assert not wm.owns(0) and wm.donors() == []
    wm.adopt(0, params)
    assert wm.owns(0) and wm.donors() == [0]
    with pytest.raises(ValueError):
        wm.adopt(0, params)  # double-adopt is a bookkeeping bug
    wm.release(0)
    assert not wm.owns(0)


def test_d2d_requires_live_donor(stack):
    """Scale-from-zero: no live donor -> d2d must fail loudly (the
    Scaler/Cluster fall back to disk, they never alias a dead tree)."""
    model, params = stack
    wm = WeightManager(params)
    with pytest.raises(ValueError):
        wm.provision(1, "d2d")
    wm.adopt(0, params)
    p1, _ = wm.provision(1, "d2d", donor=0)
    wm.release(0)
    with pytest.raises(ValueError):
        wm.provision(2, "d2d", donor=0)  # donor scaled in since
    with pytest.raises(ValueError):
        wm.provision(1, "cpu")  # wid already owns a tree
    with pytest.raises(ValueError):
        wm.provision(3, "nvlink")  # unknown strategy


def test_disk_strategy_round_trips_the_checkpoint(stack):
    """The disk transport really loads from the on-disk checkpoint the
    manager wrote at init (scale-from-zero survives donor loss)."""
    model, params = stack
    wm = WeightManager(params)
    from repro.distributed.checkpoint import checkpoint_nbytes

    assert checkpoint_nbytes(wm.ckpt_dir, 0) == wm.nbytes
    got, _ = wm.provision(7, "disk")
    for x, y in zip(jax.tree.leaves(params), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Measured transfers feed the TLManager cost model
# ---------------------------------------------------------------------------

def test_measured_transfers_feed_cost_model(stack):
    model, params = stack
    tl = TLManager()
    wm = WeightManager(params, tl=tl)
    wm.adopt(0, params)
    for wid, s in enumerate(STRATEGIES, start=1):
        wm.provision(wid, s, donor=0 if s == "d2d" else None)
        bw = tl.measured_weight_bw(s)
        assert bw is not None and bw > 0
        # the measured bandwidth now drives weight_load_time for this
        # strategy (prediction == nbytes / observed bw)
        t = tl.weight_load_time(SMOKE, s, nbytes=wm.nbytes, record=False)
        assert t == pytest.approx(wm.nbytes / bw)
    assert tl.n_weight_loads == len(STRATEGIES)


def test_weight_byte_accounting_all_strategies():
    """Satellite bugfix: every strategy moves bytes — d2d over ICI,
    cpu/disk through the host path — and record=False probes (strategy
    selection) must not inflate the counters."""
    tl = TLManager()
    n = SMOKE.param_count() * 2
    tl.weight_load_time(SMOKE, "d2d")
    assert tl.weight_bytes_moved == n
    assert tl.weight_bytes_ici == n and tl.weight_bytes_host == 0
    tl.weight_load_time(SMOKE, "cpu")
    tl.weight_load_time(SMOKE, "disk")
    assert tl.weight_bytes_moved == 3 * n
    assert tl.weight_bytes_ici == n and tl.weight_bytes_host == 2 * n
    tl.weight_load_time(SMOKE, "d2d", record=False)
    assert tl.weight_bytes_moved == 3 * n  # probe left no trace


# ---------------------------------------------------------------------------
# Cluster integration: the engine scale-out/scale-in commit paths
# ---------------------------------------------------------------------------

def _engine_cluster(**scaler_kw):
    scaler_kw.setdefault("weight_strategy", "d2d")
    return Cluster(ClusterConfig(
        model=SMOKE, n_workers=1, backend="engine",
        engine=EngineConfig.smoke(), scaling=True,
        scaler=ScalerConfig(max_workers=3, **scaler_kw),
    ))


def _force_actions(c, actions, now=1.0):
    """Drive the Cluster's commit path with canned scaler actions."""
    c.scaler.tick = lambda *a, **k: actions
    c._scaler_tick(now, c._by_wid)


def test_engine_replicas_own_their_weights(stack):
    """Ownership model: replica 0 owns the seed tree outright (adopted,
    so the cluster keeps no second device copy beside it), and every
    further replica gets its OWN tree through a transport."""
    c = Cluster(ClusterConfig(
        model=SMOKE, n_workers=2, backend="engine",
        engine=EngineConfig.smoke(),
    ))
    w0, w1 = c.workers
    assert c.weights.owns(0) and c.weights.owns(1)
    assert c.weights.params_of(0) is w0.engine.params
    assert c.weights.params_of(1) is w1.engine.params
    assert not hasattr(c, "_engine_params")
    assert _distinct_buffers(w0.engine.params, w1.engine.params)


def _live_device_bytes() -> int:
    gc.collect()
    return sum(a.nbytes for a in jax.live_arrays())


def _tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("n_workers", [1, 2])
def test_cluster_holds_one_weight_copy_per_replica(stack, n_workers):
    """The one-weight-copy invariant: after construction (warm-up
    included) the device holds each replica's params and KV pool and
    nothing else of size — no seed tree beside replica 0, no leftover
    warm-up pool."""
    before = _live_device_bytes()
    c = Cluster(ClusterConfig(
        model=SMOKE, n_workers=n_workers, backend="engine",
        engine=EngineConfig.smoke(),
    ))
    grown = _live_device_bytes() - before
    engines = [w.engine for w in c.workers]
    params = _tree_bytes(engines[0].params)
    owned = sum(_tree_bytes(e.params) + _tree_bytes(e.caches)
                for e in engines)
    assert owned == n_workers * (params + _tree_bytes(engines[0].caches))
    # page tables and decode state are a few KiB; any stray weight copy
    # or KV pool would add at least a whole tree
    slack = grown - owned
    assert 0 <= slack < min(params, _tree_bytes(engines[0].caches)), (
        grown, owned)


def test_engine_scale_out_d2d_and_scale_in_release(stack):
    """A committed d2d scale-out provisions the new replica from the
    live donor and the new engine is token-identical to the seed; a
    committed scale-in releases the owned tree and drops the engine's
    params so it stops being a donor."""
    c = _engine_cluster()
    _force_actions(c, [ScaleAction("out", "any", 0.2, strategy="d2d",
                                   warm=True)])
    assert len(c.workers) == 2
    new = c.workers[1]
    assert c.weights.owns(new.wid)
    assert _distinct_buffers(c.workers[0].engine.params,
                             new.engine.params)
    ev = [e for _, wid, e in c.timeline if wid == new.wid]
    assert any(e.startswith("scale_out:d2d") for e in ev)
    # measured provision wall time became the cold-start delay
    assert c._provision_s is not None and c._provision_s > 0

    # token identity seed vs scaled-out replica (shared jit cache)
    ref = _generate(c._engine_model, c.workers[0].engine.params,
                    c._fn_cache)
    got = _generate(c._engine_model, new.engine.params, c._fn_cache)
    assert got == ref

    # scale the new replica back in: weights reclaimed
    new.activate(1.5, "collocated")
    _force_actions(c, [ScaleAction("in", "any", 0.0,
                                   worker_id=new.wid)], now=2.0)
    assert not c.weights.owns(new.wid)
    assert new.engine.params is None
    assert c._pick_donor() == 0  # only the seed replica donates now


def test_engine_scale_from_zero_falls_back_to_disk(stack):
    """Commit-time donor re-check: the scaler may have planned d2d, but
    with every owning replica gone the Cluster provisions from disk."""
    c = _engine_cluster()
    w0 = c.workers[0]
    w0.deactivate(0.0)
    c.weights.release(0)
    w0.engine.release_weights()
    assert c._pick_donor() is None
    _force_actions(c, [ScaleAction("out", "any", 0.2, strategy="d2d",
                                   warm=True)])
    new = c.workers[1]
    assert c.weights.owns(new.wid)
    ev = [e for _, wid, e in c.timeline if wid == new.wid]
    assert any(e.startswith("scale_out:disk") for e in ev)


def test_release_weights_refuses_undrained_engine(stack):
    model, params = stack
    eng = InferenceEngine(model, params, EngineConfig.smoke(),
                          fn_cache={})
    eng.submit(Request.from_prompt(0, _prompt(), max_new=3))
    with pytest.raises(RuntimeError):
        eng.release_weights()
    eng.run_until_done()
    eng.release_weights()
    assert eng.params is None


def test_pick_donor_prefers_least_loaded(stack):
    c = _engine_cluster()
    _force_actions(c, [ScaleAction("out", "any", 0.1, strategy="cpu",
                                   warm=True)])
    new = c.workers[1]
    new.activate(1.5, "collocated")
    # load the seed replica's queue; the idle new replica donates
    c.workers[0].engine.queue.append(
        Request.from_prompt(9, _prompt(), max_new=2))
    assert c._pick_donor() == new.wid
