"""The reduction of the program's own spans (``harness/program_spans.py``):
on hand-built timelines, and on a trace recorded on a TPU v5e.

``data/program_trace`` was written by ``record_program_trace.py`` on the
chip: three requests served by a one-layer model through the program's
served path, inside ``perfbench.window``, the third one queued behind
the first two.
"""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import program_spans as ps  # noqa: E402
from harness import trace  # noqa: E402

DATA = BENCH / "tests" / "data" / "program_trace"


def _prog(ops, spans, window=(0.0, 10.0), runs=None, enqueues=()):
    return ps.Program(
        ops={"/device:TPU:0": [trace.Event("op", a, b) for a, b in ops]},
        window=window,
        spans=sorted((ps.Span(n, a, b, dict(args)) for n, a, b, args
                      in spans), key=lambda s: (s.start, -s.end)),
        runs=runs or {}, enqueues=sorted(enqueues))


def _step(t0, step, first_rids=None, kind="decode"):
    """An engine step at ``t0``: plan 1, launch 1, wait 4, commit 1."""
    args = {"kind": kind, "k": 1, "step": step}
    if first_rids is not None:
        args["first_rids"] = first_rids
    return [("hfx.engine.step", t0, t0 + 7, args),
            ("hfx.engine.plan", t0, t0 + 1, {}),
            ("hfx.engine.launch", t0 + 1, t0 + 2, {}),
            ("hfx.engine.wait", t0 + 2, t0 + 6, {}),
            ("hfx.engine.commit", t0 + 6, t0 + 7, {})]


def test_innermost_pieces_follow_nesting():
    prog = _prog([], [("hfx.cluster.event", 0, 10, {})] + _step(1, 1))
    pieces = ps.innermost(prog.spans)
    assert [p[2] for p in pieces] == [
        "hfx.cluster.event", "hfx.engine.plan", "hfx.engine.launch",
        "hfx.engine.wait", "hfx.engine.commit", "hfx.cluster.event"]
    assert sum(b - a for a, b, _ in pieces) == 10


def test_one_gap_is_split_across_the_spans_over_it_by_time():
    # the device runs 3..7 (one dispatch); the gap 7..10 falls over the
    # commit (7..8) and then the cluster event around it (8..9), and
    # 9..10 is under no program span; 0..3 over the event and the plan
    # and launch
    prog = _prog([(3, 7)], [("hfx.cluster.event", 0, 9, {})]
                 + _step(1, 1), window=(0, 10))
    r = ps.reduce(prog)
    assert r.idle_s == pytest.approx({"control": 2.0, "engine": 3.0,
                                      "outside": 1.0})
    assert r.self_s["hfx.engine.wait"] == 4
    total = sum(r.share(k) for k in ps.GROUPS + ("outside",))
    assert total == pytest.approx(60.0)     # 6 of 10 idle


def test_queue_wait_pairs_submit_end_with_first_chunk_launch():
    spans = [("hfx.session.submit", 0.5, 1.0, {"rid": 7}),
             ("hfx.session.submit", 1.5, 2.0, {"rid": 8}),
             ("hfx.session.submit", 2.5, 3.0, {"rid": 9})]
    spans += _step(4, 1, first_rids="7;8", kind="prefill_chunk")
    spans += _step(12, 2, first_rids=9, kind="prefill_chunk")
    # rid 8 again (a recompute re-prefill): its first launch counts
    spans += _step(20, 3, first_rids=8, kind="prefill_chunk")
    prog = _prog([], spans, window=(0, 30))
    assert ps.queue_waits(prog) == {7: 4.0, 8: 3.0, 9: 10.0}
    # both ends must lie in the window
    assert ps.queue_waits(_prog([], spans, window=(1.2, 30))) == {
        8: 3.0, 9: 10.0}


def test_a_device_clock_running_early_is_found_and_shifted_out():
    # each dispatch is enqueued 0.2 ms after its launch returns (at 2.2
    # ms of its step) and runs 2.5..5.5 ms on the host's clock, but the
    # device stamps it 2 ms early: "before" its launch starts
    spans, runs, enq = [], {}, []
    for i, t0 in enumerate((0.0, 0.010, 0.020)):
        spans += [(n, t0 + a * 1e-3, t0 + b * 1e-3, args)
                  for n, a, b, args in _step(0, i + 1)]
        enq.append((t0 + 2.2e-3, 40 + i))
        runs[40 + i] = (t0 + 0.5e-3, t0 + 3.5e-3)
    ops = [runs[r] for r in runs]
    prog = _prog(ops, spans, window=(0.0, 0.03), runs=runs, enqueues=enq)
    check = ps.clock_check(prog)
    assert check["dispatches"] == 3 and check["share_missing"] == 1.0
    assert check["largest_miss_ms"] == pytest.approx(0.5)
    # the enqueue bounds the offset from below (2.2 - 0.5 ms), the
    # wait's end from above (6 - 3.5 ms); the estimate is their middle
    assert check["offset_bounds_ms"] == pytest.approx([1.7, 2.5])
    assert ps.offset(prog) == pytest.approx(2.1e-3)
    assert ps.clock_check(prog, shift=ps.offset(prog))["share_missing"] == 0
    r = ps.reduce(prog)
    assert r.offset_s == pytest.approx(2.1e-3)
    # shifted, each run covers 2.6..5.6 ms of its step's 7: the wait's
    # head (0.6, cut to 0.5 by the window's start for the first) and
    # tail (0.4) are idle, and so are the commit and then the next
    # step's plan and launch (the first step's lie before the window)
    assert r.idle_s["wait"] == pytest.approx(2.9e-3)
    assert r.idle_s["engine"] == pytest.approx(7e-3)


@pytest.fixture(scope="module")
def recorded():
    prog = ps.load(DATA)
    return prog, ps.reduce(prog), trace.summarize(trace.load(DATA))


def test_the_recorded_shares_add_up_to_the_idle_share(recorded):
    prog, r, summary = recorded
    idle = 100.0 * (1.0 - summary.busy_mean_s() / summary.window_s)
    shares = [r.share(k) for k in ps.GROUPS + ("outside",)]
    assert sum(shares) == pytest.approx(idle, abs=1e-6)
    assert all(s >= 0 for s in shares) and r.share("wait") > 0


def test_the_recorded_device_clock_is_shifted_onto_the_hosts(recorded):
    prog, r, _ = recorded
    plain = ps.clock_check(prog)
    assert plain["dispatches"] >= 3
    # unshifted, every run starts on the device before the host enqueued
    # it: the device's stamps read early.  Shifted by the estimate, each
    # lies after its enqueue and before its wait's end
    lo, hi = plain["offset_bounds_ms"]
    assert 0 < lo <= 1e3 * r.offset_s <= hi
    shifted = ps.clock_check(prog, shift=r.offset_s)
    assert shifted["share_missing"] == 0


def test_the_recorded_queue_waits(recorded):
    prog, r, _ = recorded
    waits = r.queue_wait_s
    assert sorted(waits) == [100, 101, 102]
    assert all(w > 0 for w in waits.values())
    # two prompts at a time: the third waits for a slot the longest
    assert waits[102] == max(waits.values())
