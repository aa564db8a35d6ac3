"""Fault injection + recovery (PR 9).

Covers the FaultInjector spec grammar and determinism, replica-crash
recovery on both planes (engine token identity included), KV-transfer
retry with alternate destinations, crash races with migrations in
flight, last-weight-owner death (disk scale-from-zero), SLO-ordered
mass re-admission, the weight-provisioning fallback chain, donor
selection guards, the checkpoint staging-dir sweep, terminal
FAILED/RETRIED stream semantics, and the hardened online JSONL loop.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core.faults import FaultInjector
from repro.core.request import Request, RequestState
from repro.core.scaler import ScalerConfig
from repro.serving.cluster import Cluster, ClusterConfig
from repro.serving.metrics import StreamingStats, compute_metrics
from repro.serving.session import EventKind, ServingSession

MODEL = get_config("qwen7b")
SMOKE = get_smoke_config("qwen7b")


def _req(rid, arrival=0.0, l_in=200, l_out=30, ttft=10.0, tpot=0.5,
         task="t"):
    return Request(rid=rid, task=task, arrival=arrival, l_in=l_in,
                   l_out=l_out, ttft_slo=ttft, tpot_slo=tpot)


def _burst(n, seed=3, qps=30.0, **kw):
    rng = np.random.default_rng(seed)
    t, reqs = 0.0, []
    for i in range(n):
        t += float(rng.exponential(1.0 / qps))
        reqs.append(_req(i, arrival=t, l_in=int(rng.integers(150, 350)),
                         l_out=int(rng.integers(20, 40)), **kw))
    return reqs


def _run(reqs, *, spec=None, recovery=True, seed=3, **cfg_kw):
    faults = FaultInjector.from_spec(spec, seed=seed) if spec else None
    cfg = ClusterConfig(model=MODEL, policy="hyperflexis", seed=seed,
                        faults=faults, recovery=recovery, **cfg_kw)
    return Cluster(cfg).run(reqs)


# ---------------------------------------------------------------------------
# FaultInjector: spec grammar + determinism
# ---------------------------------------------------------------------------

def test_fault_spec_parsing_round_trip():
    fi = FaultInjector.from_spec(
        "crash:wid=1,t=2.0; kv_drop:p=0.5,max=3;"
        "weight_fail:strategy=d2d,p=1.0;"
        "straggler:wid=0,slowdown=4.0,t=1.0,until=6.0", seed=9,
    )
    assert [(c.wid, c.t) for c in fi.crashes] == [(1, 2.0)]
    assert fi.kv_drop_p == 0.5 and fi.kv_drop_max == 3
    assert fi.weight_fail_p == {"d2d": 1.0}
    s = fi.stragglers[0]
    assert (s.wid, s.slowdown, s.t, s.until) == (0, 4.0, 1.0, 6.0)


def test_fault_spec_errors_are_loud():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultInjector.from_spec("explode:wid=1")
    with pytest.raises(ValueError, match="missing field"):
        FaultInjector.from_spec("crash:wid=1")  # no t
    with pytest.raises(ValueError, match="key=value"):
        FaultInjector.from_spec("crash:wid")
    with pytest.raises(ValueError, match="not in"):
        FaultInjector(kv_drop_p=1.5)


def test_injector_streams_deterministic_and_independent():
    def draws(fi):
        return [fi.drop_kv_transfer(0.0, i, 0, 1) for i in range(40)]

    a = FaultInjector(kv_drop_p=0.4, seed=7)
    b = FaultInjector(kv_drop_p=0.4, seed=7)
    ref = draws(a)
    assert ref == draws(b)
    # adding a crash + weight-fail schedule must not reshuffle which
    # transfers drop (independent per-class streams)
    c = FaultInjector(kv_drop_p=0.4, seed=7,
                      crashes=[(0, 1.0)], weight_fail_p={"*": 0.5})
    for _ in range(10):
        c.fail_weight_load(0.0, "d2d")
    assert ref == draws(c)


def test_kv_drop_cap_bounds_injections():
    fi = FaultInjector(kv_drop_p=1.0, kv_drop_max=2, seed=0)
    hits = sum(fi.drop_kv_transfer(0.0, i, 0, 1) for i in range(10))
    assert hits == 2
    assert fi.n_injected == 2


def test_straggler_windows_compound_and_note_once():
    fi = FaultInjector(stragglers=[(0, 3.0, 1.0, 5.0),
                                   (0, 2.0, 2.0, 4.0)])
    assert fi.slowdown(0, 0.5) == 1.0       # before the window
    assert fi.slowdown(0, 1.5) == 3.0
    assert fi.slowdown(0, 3.0) == 6.0       # overlap compounds
    assert fi.slowdown(0, 5.0) == 1.0       # window is half-open
    assert fi.slowdown(1, 3.0) == 1.0       # other worker untouched
    assert fi.n_injected == 2               # one record per entry


# ---------------------------------------------------------------------------
# Sim-plane crash recovery
# ---------------------------------------------------------------------------

def test_sim_crash_recovery_requeues_everything():
    res = _run(_burst(40), spec="crash:wid=1,t=0.3", n_workers=2)
    m = res.metrics
    assert m.n_finished + m.n_failed == 40
    assert m.n_failed == 0 and res.n_lost == 0
    assert res.n_recovered > 0
    assert res.n_faults == 1
    assert any(ev == "crash" for _, wid, ev in res.timeline if wid == 1)


def test_sim_crash_recovery_off_sheds_residents():
    on = _run(_burst(40), spec="crash:wid=1,t=0.3", n_workers=2)
    off = _run(_burst(40), spec="crash:wid=1,t=0.3", n_workers=2,
               recovery=False)
    assert off.metrics.n_finished + off.metrics.n_failed == 40
    assert off.n_lost > 0 and off.metrics.n_failed == off.n_lost
    assert on.metrics.n_finished > off.metrics.n_finished


def test_sim_crash_during_monolithic_prefill_not_stranded():
    # regression: a monolithic prefill batch lives inside the in-flight
    # StepOutcome, not in any worker pool — a crash mid-step must still
    # re-home it (drop_all returns the in-flight batch)
    reqs = _burst(60, qps=60.0)
    res = _run(reqs, spec="crash:wid=1,t=0.1", n_workers=2)
    assert res.metrics.n_finished + res.metrics.n_failed == 60
    assert all(r.state in (RequestState.FINISHED, RequestState.FAILED)
               for r in reqs)


def test_chunked_plane_crash_recovery():
    res = _run(_burst(40), spec="crash:wid=1,t=0.3", n_workers=2,
               chunk_tokens=256)
    assert res.metrics.n_finished + res.metrics.n_failed == 40
    assert res.n_recovered > 0


def test_crash_of_only_worker_without_scaler_sheds():
    # nothing can ever serve the residents again: SLO-aware re-admission
    # must shed them as FAILED, not park them forever
    res = _run(_burst(10), spec="crash:wid=0,t=0.05", n_workers=1)
    m = res.metrics
    assert m.n_finished + m.n_failed == 10
    assert m.n_failed > 0 and res.n_lost == m.n_failed


def test_straggler_degrades_attainment_deterministically():
    base = _run(_burst(40), n_workers=2)
    a = _run(_burst(40), spec="straggler:wid=0,slowdown=6.0", n_workers=2)
    b = _run(_burst(40), spec="straggler:wid=0,slowdown=6.0", n_workers=2)
    assert a.metrics.attainment <= base.metrics.attainment
    assert a.metrics.mean_e2e == b.metrics.mean_e2e  # replayable
    assert a.n_faults == 1


# ---------------------------------------------------------------------------
# Stream semantics: no hung consumer, terminal FAILED, RETRIED events
# ---------------------------------------------------------------------------

def test_no_hung_events_consumer_after_crash():
    faults = FaultInjector.from_spec("crash:wid=1,t=0.2", seed=3)
    cfg = ClusterConfig(model=MODEL, policy="hyperflexis", n_workers=2,
                        seed=3, faults=faults)
    s = ServingSession(Cluster(cfg), admission="none")
    handles = [s.submit_request(r) for r in _burst(30)]
    s.drain()
    for h in handles:
        assert h.done, f"rid {h.rid} never reached a terminal event"
        kinds = [ev.kind for ev in h.events(wait=False)]
        assert kinds[-1] in (EventKind.FINISHED, EventKind.FAILED,
                             EventKind.REJECTED)
    s.close()


def test_failed_event_is_terminal_with_reason():
    faults = FaultInjector.from_spec("crash:wid=0,t=0.05", seed=3)
    cfg = ClusterConfig(model=MODEL, policy="hyperflexis", n_workers=1,
                        seed=3, faults=faults)
    s = ServingSession(Cluster(cfg), admission="none")
    handles = [s.submit_request(r) for r in _burst(8)]
    s.drain()
    failed = [h for h in handles if h.failed]
    assert failed, "expected at least one shed request"
    for h in failed:
        last = h.log[-1]
        assert last.kind == EventKind.FAILED
        assert "reason" in last.data
    res = s.close()
    assert s.streaming.n_failed == len(failed)
    assert res.metrics.n_failed == len(failed)


def test_retried_event_emitted_on_requeue():
    faults = FaultInjector.from_spec("crash:wid=1,t=0.2", seed=3)
    cfg = ClusterConfig(model=MODEL, policy="hyperflexis", n_workers=2,
                        seed=3, faults=faults)
    s = ServingSession(Cluster(cfg), admission="none")
    handles = [s.submit_request(r) for r in _burst(30)]
    s.drain()
    retried = [h for h in handles
               if any(ev.kind == EventKind.RETRIED for ev in h.log)]
    assert retried, "expected RETRIED events for re-queued residents"
    for h in retried:
        assert h.request.state == RequestState.FINISHED
        ev = next(ev for ev in h.log if ev.kind == EventKind.RETRIED)
        assert ev.data["reason"] == "crash"
    assert s.streaming.n_retried >= len(retried)
    s.close()


def test_streaming_stats_failed_and_retried_counters():
    st = StreamingStats()
    st.observe("first_token", 1, 0.1, arrival=0.0)
    st.observe("retried", 1, 0.2)
    # the recovery gap must not pollute inter-token latency samples
    st.observe("first_token", 1, 0.9, arrival=0.0)
    st.observe("failed", 2, 0.3)
    row = st.row()
    assert row["n_retried"] == 1 and row["n_failed"] == 1


def test_compute_metrics_counts_failed_against_attainment():
    a, b = _req(0), _req(1)
    a.first_token_time, a.finish_time = 0.1, 1.0
    a.tokens_done, a.state = a.l_out, RequestState.FINISHED
    b.state = RequestState.FAILED
    m = compute_metrics([a, b], 0.0, 1.0)
    assert m.n_failed == 1 and m.n_total == 2
    assert m.attainment <= 0.5


# ---------------------------------------------------------------------------
# KV-transfer drops: retry, alternate destination, fallback
# ---------------------------------------------------------------------------

def test_kv_drop_retries_on_alternate_destination():
    res = _run(_burst(30), spec="kv_drop:p=1.0,max=2", mode="pd",
               n_prefill=1, n_decode=2)
    assert res.metrics.n_finished + res.metrics.n_failed == 30
    assert res.n_lost == 0
    assert res.n_transfer_retries >= 2
    # each retry re-places the transfer, avoiding the destination of
    # the drop that immediately preceded it for that request
    last_drop: dict = {}
    checked = 0
    for _, _, ev in res.timeline:
        if ev.startswith("kv_drop:"):
            rid, dst = ev.split(":")[1].split("->")
            last_drop[rid] = dst
        elif ev.startswith("kv_retry_to:"):
            rid, dst = ev.split(":")[1].split("->")
            assert dst != last_drop[rid]
            checked += 1
    assert checked >= 2


def test_kv_drop_exhausted_retries_fall_back():
    from repro.serving.recovery import RecoveryConfig

    faults = FaultInjector.from_spec("kv_drop:p=1.0,max=4", seed=3)
    cfg = ClusterConfig(model=MODEL, policy="hyperflexis", mode="pd",
                        n_prefill=1, n_decode=2, seed=3, faults=faults,
                        recovery_cfg=RecoveryConfig(
                            max_transfer_retries=0))
    res = Cluster(cfg).run(_burst(20))
    assert res.metrics.n_finished + res.metrics.n_failed == 20
    assert res.n_transfer_retries == 0
    assert any(ev.startswith("kv_giveup:") for _, _, ev in res.timeline)


def test_crash_of_decode_worker_with_transfers_in_flight():
    # hand-offs racing toward the corpse: their ledger charges are
    # dropped and the stale kv_ready events no-op; sources re-home
    res = _run(_burst(30, qps=60.0), spec="crash:wid=1,t=0.15",
               mode="pd", n_prefill=1, n_decode=2)
    assert res.metrics.n_finished + res.metrics.n_failed == 30
    assert res.n_lost == 0


def test_crash_of_prefill_source_with_transfers_in_flight():
    # the source dies mid-flight: the crashed-src guard stops the
    # export and crash recovery re-prefills the residents elsewhere
    res = _run(_burst(30, qps=60.0), spec="crash:wid=0,t=0.15",
               mode="pd", n_prefill=2, n_decode=1)
    assert res.metrics.n_finished + res.metrics.n_failed == 30


def test_live_migration_survives_crash_and_drops():
    res = _run(_burst(40, qps=80.0),
               spec="crash:wid=1,t=0.3;kv_drop:p=0.5,max=3",
               n_workers=3, live_migration=True)
    assert res.metrics.n_finished + res.metrics.n_failed == 40
    assert res.n_faults >= 1


# ---------------------------------------------------------------------------
# Mass re-admission ordering
# ---------------------------------------------------------------------------

def test_readmission_orders_by_tpot_then_arrival(monkeypatch):
    cfg = ClusterConfig(model=MODEL, policy="hyperflexis", n_workers=2,
                        seed=0)
    cl = Cluster(cfg)
    w = cl.workers[1]
    residents = [
        _req(0, arrival=0.3, tpot=0.5),
        _req(1, arrival=0.1, tpot=0.1),
        _req(2, arrival=0.2, tpot=0.1),
        _req(3, arrival=0.0, tpot=0.9),
    ]
    for r in residents:
        r.state = RequestState.DECODING
        r.prefill_worker = r.decode_worker = w.wid
        r.first_token_time, r.tokens_done = 0.05, 3
        w.running.append(r)
    order = []
    orig = cl.policy.on_request_arrive
    monkeypatch.setattr(
        cl.policy, "on_request_arrive",
        lambda r: (order.append(r.rid), orig(r))[1],
    )
    w.crashed = True
    w.deactivate(1.0)
    cl.recovery.note_crash(w.wid, 1.0)
    cl.recovery.watchdog(1.0)
    assert order == [1, 2, 0, 3]  # (tpot_slo, arrival) lexicographic
    assert cl.recovery.n_recovered == 4


def test_requeue_keeps_original_arrival_and_first_token():
    res = _run(_burst(40), spec="crash:wid=1,t=0.3", n_workers=2)
    reqs = res.requests
    # arrival stamps survive the re-queue: attainment is judged against
    # the true submit time, not the recovery time
    assert all(r.arrival is not None and r.arrival < 2.0 for r in reqs)
    assert all(r.state == RequestState.FINISHED for r in reqs)


# ---------------------------------------------------------------------------
# Weight-provisioning faults + donor guards (engine plane)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_cluster():
    from repro.serving.engine import EngineConfig

    cfg = ClusterConfig(
        model=SMOKE, backend="engine", n_workers=2,
        policy="hyperflexis", seed=0,
        engine=EngineConfig(n_slots=4, max_len=48, prefill_batch=2,
                            page_size=8, chunk_size=16),
        faults=FaultInjector(weight_fail_p={"d2d": 1.0}, seed=0),
    )
    return Cluster(cfg)


def test_weight_fail_falls_back_down_the_chain(engine_cluster):
    cl = engine_cluster
    w = cl._make_worker(90, "collocated", active=False,
                        strategy="d2d", donor=0)
    # injected d2d failure -> the cpu offload serves the copy
    assert cl._provision_strategy == "cpu"
    assert cl.weights.owns(90)
    assert any("weight_fail:d2d" in str(ev)
               for _, wid, ev in cl.timeline if wid == 90)
    cl.weights.release(90)
    w.engine.release_weights()


def test_dead_donor_mid_pull_falls_back(engine_cluster):
    cl = engine_cluster
    # donor wid no longer owns a tree: the d2d pull itself raises and
    # the chain falls through (cpu is also scripted dead here? no —
    # only d2d has p=1.0, but the injected skip already covers d2d;
    # exercise the *exception* path with a fault-free injector)
    saved = cl.faults
    cl.faults = None
    try:
        cl._make_worker(91, "collocated", active=False,
                        strategy="d2d", donor=777)  # bogus donor
        assert cl._provision_strategy in ("cpu", "disk")
        assert cl.weights.owns(91)
    finally:
        cl.faults = saved
        cl.weights.release(91)


def test_pick_donor_skips_evacuating_and_crashed(engine_cluster):
    cl = engine_cluster
    w0, w1 = cl.workers[0], cl.workers[1]
    assert cl._pick_donor() in (w0.wid, w1.wid)
    w0.evacuating = True
    assert cl._pick_donor() == w1.wid
    w1.crashed = True
    assert cl._pick_donor() is None
    w0.evacuating = w1.crashed = False


# ---------------------------------------------------------------------------
# Engine plane end to end: crash recovery is token-exact
# ---------------------------------------------------------------------------

def _engine_run(spec, recovery=True, n=14, seed=5):
    from repro.serving.workload import engine_smoke_workload

    reqs = engine_smoke_workload(n=n, qps=2000.0, seed=seed, clip_out=20)
    faults = FaultInjector.from_spec(spec, seed=seed) if spec else None
    cfg = ClusterConfig(model=SMOKE, backend="engine", n_workers=2,
                        policy="hyperflexis", seed=seed, faults=faults,
                        recovery=recovery, monitor_interval=0.005)
    res = Cluster(cfg).run(reqs)
    return res, {r.rid: list(r.generated) for r in reqs}


def test_engine_crash_recovery_token_identical():
    base, base_toks = _engine_run(None)
    assert base.metrics.n_finished == 14
    res, toks = _engine_run("crash:wid=1,t=0.01")
    assert res.metrics.n_finished + res.metrics.n_failed == 14
    assert res.n_recovered > 0 and res.n_lost == 0
    # greedy decode + prompt folding: recovered streams re-emit the
    # exact tokens of the fault-free run
    assert toks == base_toks


def test_engine_crash_recovery_off_sheds():
    res, _ = _engine_run("crash:wid=1,t=0.01", recovery=False)
    assert res.metrics.n_failed > 0
    assert res.metrics.n_finished + res.metrics.n_failed == 14


def test_engine_last_weight_owner_crash_scales_from_disk():
    from repro.serving.engine import EngineConfig
    from repro.serving.workload import engine_smoke_workload

    reqs = engine_smoke_workload(n=8, qps=2000.0, seed=4, clip_out=8)
    faults = FaultInjector.from_spec("crash:wid=0,t=0.01", seed=4)
    cfg = ClusterConfig(
        model=SMOKE, backend="engine", n_workers=1,
        policy="hyperflexis", seed=4, faults=faults,
        monitor_interval=0.005, scaling=True,
        scaler=ScalerConfig(tau=0.02, max_workers=2,
                            weight_strategy="d2d"),
        engine=EngineConfig(n_slots=4, max_len=48, prefill_batch=2,
                            page_size=8, chunk_size=16),
    )
    res = Cluster(cfg).run(reqs)
    # the only weight owner died: the first scale-out must come from
    # disk (later ones may d2d off the freshly provisioned replica)
    outs = [ev for _, _, ev in res.timeline
            if ev.startswith("scale_out:")]
    assert outs and "disk" in outs[0]
    assert res.metrics.n_finished + res.metrics.n_failed == 8
    assert res.metrics.n_finished > 0


def test_engine_crash_mid_step_completion_not_stranded():
    """The engine executes steps eagerly: a request can complete (and
    leave every engine pool) while its step is still in flight in
    cluster time.  A crash landing in that window must re-home it —
    not strand its handle until the drain horizon.  The straggler
    stretches w1's step durations so the crash deterministically
    precedes the first step_done; l_out=1 makes the request complete
    inside its own prefill step."""
    from repro.serving.workload import engine_smoke_workload

    reqs = engine_smoke_workload(n=8, qps=2000.0, seed=6, clip_out=1)
    faults = FaultInjector.from_spec(
        "straggler:wid=1,slowdown=1e6;crash:wid=1,t=0.05", seed=6
    )
    cfg = ClusterConfig(model=SMOKE, backend="engine", n_workers=2,
                        policy="hyperflexis", seed=6, faults=faults,
                        monitor_interval=0.005, drain_timeout=5.0)
    res = Cluster(cfg).run(reqs)
    assert res.metrics.n_finished + res.metrics.n_failed == 8
    assert res.n_recovered > 0
    # no orphaned handle rode the drain horizon
    assert res.metrics.makespan < 5.0


# ---------------------------------------------------------------------------
# Checkpoint staging-dir sweep
# ---------------------------------------------------------------------------

def test_load_latest_sweeps_stale_tmp_dirs(tmp_path):
    import jax.numpy as jnp

    from repro.distributed.checkpoint import (
        load_latest,
        save_checkpoint,
    )

    tree = {"w": jnp.ones((2, 2))}
    save_checkpoint(str(tmp_path), 3, tree)
    stale = tmp_path / ".tmp_dead_writer"
    stale.mkdir()
    (stale / "arrays.npz").write_bytes(b"partial")
    out = load_latest(str(tmp_path), tree)
    assert out is not None and out[0] == 3
    assert not stale.exists()


def test_load_latest_sweep_on_empty_dir(tmp_path):
    from repro.distributed.checkpoint import load_latest

    stale = tmp_path / ".tmp_x"
    stale.mkdir()
    assert load_latest(str(tmp_path), {"w": np.ones(2)}) is None
    assert not stale.exists()


# ---------------------------------------------------------------------------
# Online JSONL hardening + fault flags (CLI)
# ---------------------------------------------------------------------------

def test_online_malformed_jsonl_survives():
    env = dict(os.environ, PYTHONPATH="src",
               JAX_ENABLE_COMPILATION_CACHE="false")
    lines = "\n".join([
        "this is not json",
        '{"task":"gsm8k","l_in":12,"l_out":3}',
        '[1,2,3]',
        '{"task":"gsm8k","l_in":"not-a-length","l_out":3}',
        '{"task":"gsm8k","l_in":10,"l_out":2}',
    ]) + "\n"
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--online",
         "--model", "qwen7b", "--workers", "1", "--admission", "none"],
        input=lines, capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr
    evs = [json.loads(ln) for ln in out.stdout.splitlines() if ln]
    errors = [e for e in evs if e["event"] == "error"]
    summary = [e for e in evs if e["event"] == "summary"]
    assert len(errors) == 3
    assert all("reason" in e and "line" in e for e in errors)
    assert summary and summary[0]["n_finished"] == 2


def test_serve_fault_schedule_cli_sim():
    env = dict(os.environ, PYTHONPATH="src",
               JAX_ENABLE_COMPILATION_CACHE="false")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--model", "qwen7b",
         "--workers", "2", "--qps", "40", "--n-per-task", "8",
         "--tasks", "2task", "--fault-schedule", "crash:wid=1,t=0.3",
         "--json"],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["n_faults"] == 1
    assert row["n_finished"] + row["n_failed"] + row["n_rejected"] \
        == row["n_total"]
