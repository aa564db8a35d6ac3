"""The program's own spans (``hfx.*``) in a traced run, on the device's clock.

The program marks each layer boundary of its served path with a
``jax.profiler.TraceAnnotation`` named ``hfx.<layer>.<phase>``
(``src/repro/tracing.py``).  They land in the ``.xplane.pb`` that
``run.py`` writes under ``perfbench/.trace``, beside the device ops.
This module reads that file once per process and gives:

- idle apportioned by time: each instant of a device gap inside
  ``perfbench.window`` goes to the innermost ``hfx.*`` span open on the
  host at that instant, or to "outside the program" where none is.
  Spans fall in three groups: ``wait`` (``hfx.engine.wait``: the host
  blocked on the device), ``engine`` (the rest of ``hfx.engine.*``, and
  ``hfx.kv.*``) and ``control`` (every other ``hfx.*`` span:
  ``hfx.session.*``, ``hfx.cluster.*``, ``hfx.dispatch.*``).  The gaps
  are those of ``harness.trace.summarize``, so on each device the three
  groups and the outside add up to its idle time.
- self time per span name: the time it is the innermost span, inside
  the window.
- queue wait per request: from the end of its ``hfx.session.submit`` to
  the start of the ``hfx.engine.launch`` of the step whose
  ``first_rids`` holds it; counted where both lie inside the window.
- a clock check, and the device ops put on the host's clock.  The
  trace's device timestamps read early against the host's: by 0.4-1.1
  ms in the cells' traced runs on a v5e, 1.2-1.7 ms in
  ``tests/data/program_trace``.  Each dispatch's program run is found by
  its ``run_id``: the runtime's host event ``DoEnqueueProgram`` between
  the start of the step's ``hfx.engine.launch`` and the end of its
  ``hfx.engine.wait`` (the enqueue itself is asynchronous and often
  lands after the launch returns) names the run on the device's ``XLA
  Modules`` line.  The check asks that the run start after the launch
  starts and end before the wait ends.  The enqueue bounds the offset
  from below (no program runs before it is enqueued) and the wait's
  end from above; the device ops are shifted by the middle of the
  bounds that every dispatch allows (of the median bounds, where no
  offset fits them all) before idle is apportioned, whatever its size,
  so that the shares have no threshold to jump at.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import statistics
from pathlib import Path
from typing import Optional

from harness import trace

TRACE_DIR = Path(__file__).resolve().parents[1] / ".trace"
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
GROUPS = ("wait", "engine", "control")


@dataclasses.dataclass
class Span:
    name: str
    start: float            # seconds, host clock
    end: float
    args: dict


@dataclasses.dataclass(eq=False)
class Program:
    ops: dict               # device plane -> list[trace.Event] (its clock)
    window: tuple           # perfbench.window (start, end), host clock
    spans: list             # hfx.* Span, by start
    runs: dict              # run_id -> (start, end) on the device clock
    enqueues: list          # (host time, run_id) of each program enqueued


def group(name: str) -> str:
    if name == "hfx.engine.wait":
        return "wait"
    if name.startswith(("hfx.engine.", "hfx.kv.")):
        return "engine"
    return "control"


def load(trace_dir=TRACE_DIR) -> Program:
    """The traced run's device ops, window and program spans; read once
    per file (and again only if it changes)."""
    files = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{trace_dir}, found {files}")
    st = Path(files[0]).stat()
    return _load(str(trace_dir), files[0], st.st_mtime_ns, st.st_size)


@functools.lru_cache(maxsize=2)
def _load(trace_dir: str, path: str, mtime_ns: int, size: int) -> Program:
    from jax.profiler import ProfileData

    tr = trace.load(trace_dir)
    (w,) = [s for s in tr.spans if s.name == trace.WINDOW_SPAN]
    spans, runs, enqueues = [], {}, []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != MODULES_LINE:
                continue
            for e in line.events:
                t0 = e.start_ns * 1e-9
                t1 = (e.start_ns + e.duration_ns) * 1e-9
                if device:
                    run = dict(e.stats).get("run_id")
                    if run is not None:
                        a, b = runs.get(run, (t0, t1))
                        runs[run] = (min(a, t0), max(b, t1))
                elif e.name.startswith("hfx."):
                    spans.append(Span(e.name, t0, t1, dict(e.stats)))
                elif e.name == ENQUEUE:
                    run = dict(e.stats).get("run_id")
                    if run is not None:
                        enqueues.append((t0, run))
    spans.sort(key=lambda s: (s.start, -s.end))
    enqueues.sort()
    return Program(ops=tr.devices, window=(w.start, w.end), spans=spans,
                   runs=runs, enqueues=enqueues)


# -- the host timeline ----------------------------------------------------------
def innermost(spans) -> list:
    """(start, end, name) pieces of the host timeline, each under one
    innermost span; time under no span is left out."""
    evs = []
    for i, s in enumerate(spans):
        # at one instant: ends before starts, inner ends and outer
        # starts first
        evs.append((s.start, 1, -s.end, i))
        evs.append((s.end, 0, -s.start, i))
    evs.sort()
    out, stack, last = [], [], None
    for t, is_start, _, i in evs:
        if stack and t > last:
            out.append((last, t, spans[stack[-1]].name))
        last = t
        if is_start:
            stack.append(i)
        else:
            stack.remove(i)
    return out


def _overlap(pieces, gaps, shift: float) -> dict:
    """Seconds of ``gaps`` (sorted, disjoint) under each piece's name,
    the pieces moved by ``-shift`` onto the gaps' clock."""
    out: dict[str, float] = {}
    j = 0
    for a, b, name in pieces:
        a, b = a - shift, b - shift
        while j < len(gaps) and gaps[j][1] <= a:
            j += 1
        k = j
        while k < len(gaps) and gaps[k][0] < b:
            d = min(b, gaps[k][1]) - max(a, gaps[k][0])
            if d > 0:
                out[name] = out.get(name, 0.0) + d
            k += 1
    return out


def _gaps(ops, w0: float, w1: float) -> Optional[list]:
    """The device's idle intervals in the window, as ``summarize``
    finds them; None where it ran nothing there."""
    inside = [e for e in ops if e.end > w0 and e.start < w1]
    merged = trace._union((max(e.start, w0), min(e.end, w1)) for e in inside)
    if not merged:
        return None
    edges = [w0] + [x for ab in merged for x in ab] + [w1]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


# -- dispatches and the clocks ----------------------------------------------------
def _within(spans, name: str, outer: Span) -> Optional[Span]:
    for s in spans:
        if s.name == name and outer.start <= s.start and s.end <= outer.end:
            return s
    return None


def dispatches(prog: Program) -> list:
    """One record per engine step that dispatched: its span, launch,
    wait, the first enqueue between them (host clock) and the device
    clock's (start, end) of the programs enqueued (None where the trace
    names no run)."""
    out = []
    times = [t for t, _ in prog.enqueues]
    steps = [s for s in prog.spans if s.name == "hfx.engine.step"]
    inner = [s for s in prog.spans
             if s.name in ("hfx.engine.launch", "hfx.engine.wait")]
    starts = [s.start for s in inner]
    for st in steps:
        lo = bisect.bisect_left(starts, st.start)
        hi = bisect.bisect_right(starts, st.end)
        launch = _within(inner[lo:hi], "hfx.engine.launch", st)
        wait = _within(inner[lo:hi], "hfx.engine.wait", st)
        if launch is None or wait is None:
            continue
        i = bisect.bisect_left(times, launch.start)
        j = bisect.bisect_right(times, wait.end)
        runs = [(t, prog.runs[r]) for t, r in prog.enqueues[i:j]
                if r in prog.runs]
        out.append({"step": st, "launch": launch, "wait": wait,
                    "enqueued": runs[0][0] if runs else None,
                    "device": (min(a for _, (a, _) in runs),
                               max(b for _, (_, b) in runs))
                    if runs else None})
    return out


def clock_check(prog: Program, kinds=("decode",), shift: float = 0.0):
    """Each dispatch of ``kinds`` must run on the device (its clock
    moved by ``shift``) after its launch starts and before its wait
    ends: the largest miss, the share of dispatches that miss, and the
    offsets that every dispatch allows (from its enqueue and its wait's
    end)."""
    rows = [d for d in dispatches(prog) if d["device"] is not None
            and str(d["step"].args.get("kind", "")).startswith(kinds)]
    if not rows:
        return {"dispatches": 0}
    lo = [d["enqueued"] - d["device"][0] for d in rows]
    hi = [d["wait"].end - d["device"][1] for d in rows]
    miss = [max(0.0, d["launch"].start - d["device"][0] - shift,
                shift - b) for d, b in zip(rows, hi)]
    return {"dispatches": len(rows), "largest_miss_ms": 1e3 * max(miss),
            "share_missing": sum(m > 0 for m in miss) / len(rows),
            "offset_bounds_ms": [1e3 * max(lo), 1e3 * min(hi)],
            "median_bounds_ms": [1e3 * statistics.median(lo),
                                 1e3 * statistics.median(hi)]}


def offset(prog: Program) -> float:
    """Seconds to add to the device clock to put it on the host's (0
    where no dispatch names its run)."""
    c = clock_check(prog, kinds=("prefill", "decode"))
    if not c["dispatches"]:
        return 0.0
    lo, hi = c["offset_bounds_ms"]
    if lo > hi:
        lo, hi = c["median_bounds_ms"]
    return 0.5e-3 * (lo + hi)


# -- what the metrics read ----------------------------------------------------------
@dataclasses.dataclass
class Reduction:
    window_s: float
    offset_s: float
    idle_s: dict            # group or "outside" -> idle seconds, device mean
    idle_by_name: dict      # span name -> idle seconds under it, device mean
    self_s: dict            # span name -> seconds innermost, in the window
    queue_wait_s: dict      # rid -> seconds

    def share(self, key: str) -> float:
        """Percent of the traced part the device sat idle with the
        host in ``key`` (a group, or "outside")."""
        return 100.0 * self.idle_s.get(key, 0.0) / self.window_s


def reduce(prog: Program) -> Reduction:
    w0, w1 = prog.window
    shift = offset(prog)
    pieces = innermost(prog.spans)
    idle: dict[str, float] = {}
    idle_by_name: dict[str, float] = {}
    used = 0
    for ops in prog.ops.values():
        gaps = _gaps(ops, w0, w1)
        if gaps is None:
            continue
        used += 1
        by_name = _overlap(pieces, gaps, shift)
        total = sum(b - a for a, b in gaps)
        for name, sec in by_name.items():
            g = group(name)
            idle[g] = idle.get(g, 0.0) + sec
            idle_by_name[name] = idle_by_name.get(name, 0.0) + sec
        idle["outside"] = (idle.get("outside", 0.0) + total
                           - sum(by_name.values()))
    idle = {k: v / max(used, 1) for k, v in idle.items()}
    idle_by_name = {k: v / max(used, 1) for k, v in idle_by_name.items()}
    self_s: dict[str, float] = {}
    for a, b, name in pieces:
        d = min(b, w1) - max(a, w0)
        if d > 0:
            self_s[name] = self_s.get(name, 0.0) + d
    return Reduction(window_s=w1 - w0, offset_s=shift, idle_s=idle,
                     idle_by_name=idle_by_name, self_s=self_s,
                     queue_wait_s=queue_waits(prog))


def queue_waits(prog: Program) -> dict:
    w0, w1 = prog.window
    submitted = {int(s.args["rid"]): s.end for s in prog.spans
                 if s.name == "hfx.session.submit" and "rid" in s.args}
    out = {}
    for d in dispatches(prog):
        rids = d["step"].args.get("first_rids")
        t = d["launch"].start
        for rid in (str(rids).split(";") if rids is not None else ()):
            sent = submitted.get(int(rid))
            if (sent is not None and int(rid) not in out
                    and w0 <= sent and t <= w1):
                out[int(rid)] = t - sent
    return out


def reduced(trace_dir=TRACE_DIR) -> Optional[Reduction]:
    """The reduction of the traced run, or None where its trace holds
    no program span (a program that has none)."""
    prog = load(trace_dir)
    if not prog.spans:
        return None
    return _reduce(prog)


@functools.lru_cache(maxsize=2)
def _reduce(prog: Program) -> Reduction:
    return reduce(prog)


def idle_share(ctx, key: str, trace_dir=TRACE_DIR) -> Optional[float]:
    if ctx.summary is None:
        return None
    r = reduced(trace_dir)
    return None if r is None else r.share(key)


def queue_wait_ms(ctx, trace_dir=TRACE_DIR) -> Optional[float]:
    if ctx.summary is None:
        return None
    r = reduced(trace_dir)
    if r is None or not r.queue_wait_s:
        return None
    return 1e3 * statistics.fmean(r.queue_wait_s.values())


# -- the report behind PERF.md's clock check and span table ------------------------
def report(trace_dir=TRACE_DIR) -> dict:
    """Every span and argument of a traced run, summed up: the clock
    check, the idle split, self times, queue waits, and per span name
    what its arguments say (``kind``/``k``/``tokens`` per step kind,
    ``step`` links, fit ``samples``, stream ``tokens``, dispatch
    ``queued``/``placed``, table ``pages``, submit ``admitted``)."""
    prog = load(trace_dir)
    r = reduce(prog)

    def named(name):
        return [s for s in prog.spans if s.name == name]

    def mean(xs):
        xs = list(xs)
        return statistics.fmean(xs) if xs else None

    def ms(spans):
        return mean(1e3 * (s.end - s.start) for s in spans)

    def spread(xs):
        xs = sorted(xs)
        return ({"median": statistics.median(xs), "mean": mean(xs),
                 "max": xs[-1], "over_5": sum(x > 5 for x in xs)}
                if xs else None)

    steps, edges = {}, []
    for d in dispatches(prog):
        if d["device"] is not None:
            edges.append((d["device"][0] + r.offset_s - d["wait"].start,
                          d["wait"].end - d["device"][1] - r.offset_s))
        st = d["step"]
        row = steps.setdefault(str(st.args.get("kind")), [])
        row.append((st, [_within(prog.spans, n, st) for n in
                         ("hfx.engine.plan", "hfx.engine.commit")],
                    d["launch"], d["wait"]))
    events = named("hfx.cluster.event")
    done = {s.args.get("step") for s in events
            if s.args.get("kind") == "step_done"}
    started = [s.args["step"] for s in events
               if s.args.get("kind") == "worker_step" and "step" in s.args]
    fits = named("hfx.cluster.fit")
    stream = named("hfx.cluster.stream")
    passes = named("hfx.dispatch.pass")
    tables = named("hfx.kv.table")
    submits = named("hfx.session.submit")
    waits = sorted(r.queue_wait_s.values())
    return {
        "window_s": r.window_s,
        "offset_ms": 1e3 * r.offset_s,
        "clock_check": clock_check(prog),
        "clock_check_shifted": clock_check(prog, shift=r.offset_s),
        "idle_share": {k: r.share(k) for k in GROUPS + ("outside",)},
        "idle_share_by_span": {
            k: 100.0 * v / r.window_s for k, v in
            sorted(r.idle_by_name.items(), key=lambda kv: -kv[1])},
        # device idle in a wait before its run starts and after it ends
        "wait_head_ms": spread(1e3 * max(0.0, a) for a, _ in edges),
        "wait_tail_ms": spread(1e3 * max(0.0, b) for _, b in edges),
        "self_ms": {k: 1e3 * v for k, v in
                    sorted(r.self_s.items(), key=lambda kv: -kv[1])},
        "queue_wait": {
            "n": len(waits), "mean_ms": mean(1e3 * w for w in waits),
            "p50_ms": 1e3 * waits[len(waits) // 2] if waits else None,
            "p90_ms": (1e3 * waits[int(0.9 * (len(waits) - 1))]
                       if waits else None)},
        "steps": {kind: {
            "n": len(rows),
            "k": mean(st.args.get("k", 1) for st, *_ in rows),
            "tokens": mean(st.args.get("tokens", 0) for st, *_ in rows),
            "step_ms": ms(st for st, *_ in rows),
            "plan_ms": ms(p for _, (p, c), *_ in rows if p),
            "launch_ms": ms(la for *_, la, _ in rows),
            "wait_ms": ms(wa for *_, wa in rows),
            "commit_ms": ms(c for _, (p, c), *_ in rows if c),
        } for kind, rows in steps.items()},
        "worker_steps_linked": [sum(s in done for s in started),
                                len(started)],
        "fit": {"n": len(fits), "ms": ms(fits),
                "first": [fits[0].args.get("samples"),
                          1e3 * (fits[0].end - fits[0].start)] if fits
                else None,
                "last": [fits[-1].args.get("samples"),
                         1e3 * (fits[-1].end - fits[-1].start)] if fits
                else None},
        "stream": {"n": len(stream), "ms": ms(stream),
                   "tokens": mean(s.args.get("tokens", 0) for s in stream)},
        "dispatch_pass": {
            "n": len(passes), "ms": ms(passes),
            "queued": mean(s.args.get("queued", 0) for s in passes),
            "placed": sum(s.args.get("placed", 0) for s in passes)},
        "kv_table": {"n": len(tables), "ms": ms(tables),
                     "pages": mean(s.args.get("pages", 0) for s in tables)},
        "submit": {"n": len(submits), "ms": ms(submits),
                   "admitted": sum(s.args.get("admitted", 0)
                                   for s in submits)},
    }


if __name__ == "__main__":
    # from perfbench/: python3 -m harness.program_spans [trace_dir]
    import json
    import sys

    print(json.dumps(report(*sys.argv[1:2])))
