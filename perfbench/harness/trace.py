"""From a profiler trace to device busy and idle time, kernel time and gaps.

The traced window is the host span ``perfbench.window``, which the
harness opens right after the profiler starts and closes right before it
stops.  On each device plane (``/device:...``) the ops of the
``XLA Ops`` line are the device's work: busy time is the union of their
intervals inside the window, and a gap is an interval inside the window
where no op runs.  Each gap is named after the innermost ``perfbench.*``
host span that covers its midpoint: what the benchmark's host thread was
doing while the device waited.

An op's event name is its whole HLO instruction.  Ops are grouped by
the instruction's name with XLA's numeric suffix cut off
(``fusion.123`` -> ``fusion``), which a recompile keeps; a Pallas
kernel's custom call carries the name of its jitted function
(``paged_decode_attention``).  Control-flow ops (``while``) span their
bodies' ops, so they count towards busy time but not as ops of their
own.
"""

from __future__ import annotations

import dataclasses
import glob
import re
from pathlib import Path

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "perfbench."
WINDOW_SPAN = "perfbench.window"
HLO_NAME_RE = re.compile(r"%?([^\s=]+) = ")
CONTAINER_RE = re.compile(r"\) (while|conditional|call)\(")


@dataclasses.dataclass
class Event:
    name: str
    start: float        # seconds
    end: float


@dataclasses.dataclass
class Trace:
    devices: dict       # plane name -> list[Event] of its XLA ops
    spans: list         # host perfbench.* spans, list[Event]


def load(trace_dir) -> Trace:
    """Read the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{trace_dir}, found {files}")
    data = ProfileData.from_file(files[0])
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [_event(e) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [_event(e) for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return Trace(devices=devices, spans=spans)


def _event(e) -> Event:
    return Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)


def stable_name(e: Event) -> str:
    """``%paged_decode_attention.10 = f32[...] custom-call(...)`` ->
    ``paged_decode_attention``: the HLO instruction's name without
    XLA's numeric suffix (a Pallas kernel's is its jitted function's)."""
    m = HLO_NAME_RE.match(e.name)
    name = m.group(1) if m else e.name
    return re.sub(r"(\.\d+)+$", "", name)


def is_container(e: Event) -> bool:
    """A control-flow op whose interval holds its body's ops."""
    return bool(CONTAINER_RE.search(e.name))


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: dict            # device -> seconds busy inside the window
    op_s: dict              # stable op name -> device seconds, all devices
    op_calls: dict          # stable op name -> number of ops
    gaps: list              # (span name, seconds, device), longest first

    def busy_mean_s(self) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        used = [b for b in self.busy_s.values() if b > 0]
        return sum(used) / len(used) if used else 0.0

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[g[0], g[1]] for g in self.gaps[:n]]}

    def idle_by_span(self) -> dict:
        out: dict[str, float] = {}
        for name, sec, _ in self.gaps:
            out[name] = out.get(name, 0.0) + sec
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def summarize(tr: Trace) -> Summary:
    windows = [s for s in tr.spans if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    w0, w1 = windows[0].start, windows[0].end
    others = [s for s in tr.spans if s.name != WINDOW_SPAN]
    busy, op_s, op_calls, gaps = {}, {}, {}, []
    for dev, ops in tr.devices.items():
        inside = [e for e in ops if e.end > w0 and e.start < w1]
        for e in inside:
            if is_container(e):
                continue
            k = stable_name(e)
            op_s[k] = op_s.get(k, 0.0) + (min(e.end, w1) - max(e.start, w0))
            op_calls[k] = op_calls.get(k, 0) + 1
        merged = _union((max(e.start, w0), min(e.end, w1)) for e in inside)
        busy[dev] = sum(b - a for a, b in merged)
        if not merged:
            continue
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_covering(others, (a + b) / 2), b - a, dev))
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=w1 - w0, busy_s=busy, op_s=op_s,
                   op_calls=op_calls, gaps=gaps)


def _covering(spans, t: float) -> str:
    inner = [s for s in spans if s.start <= t <= s.end]
    if not inner:
        return "untraced host"
    return min(inner, key=lambda s: s.end - s.start).name
