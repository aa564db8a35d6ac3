"""What the per-layer readers (``metrics/<name>.py``) read.

A traced run hands every reader one ``Context``: the engine steps and
cluster events recorded in the measured window, the client's records,
and the reduced device trace of the traced part of it.  Each function
here returns None where there is nothing to read, and the harness then
leaves the metric out.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from harness import counts, stats


@dataclasses.dataclass
class Context:
    cell: object
    window: tuple           # (start, end) of the measured window, perf clock
    traced: tuple           # (start, end) of the traced part, perf clock
    steps: list             # StepRecord, whole run
    events: list            # (t0, t1) of each Cluster.process_next call
    clients: list           # the window's Client records
    summary: object         # trace.Summary of the traced part
    dims: counts.Dims
    kv_bytes: int           # itemsize of the KV pages as read at run time
    q_bytes: int
    peaks: dict
    replica_devices: int    # devices that hold a replica
    seed_checkpoint_bytes: Optional[float] = None  # kept from disk in set-up

    def steps_in(self, span: tuple, kind: Optional[str] = None) -> list:
        return [s for s in self.steps if span[0] <= s.t0 and s.t1 <= span[1]
                and (kind is None or s.kind == kind)]


def control_ms_per_step(ctx: Context):
    """Host time inside cluster event handling but outside
    ``EngineWorker.run_step``, per engine step."""
    steps = ctx.steps_in(ctx.window)
    steps = [s for s in steps if s.kind != "idle"]
    if not steps:
        return None
    ev = sum(b - a for a, b in ctx.events
             if ctx.window[0] <= a and b <= ctx.window[1])
    return 1e3 * (ev - sum(s.t1 - s.t0 for s in steps)) / len(steps)


def prefill_chunk_ms(ctx: Context):
    steps = ctx.steps_in(ctx.window, "prefill")
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)


def decode_iter_ms(ctx: Context):
    steps = ctx.steps_in(ctx.window, "decode")
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / sum(s.k for s in steps)


def decode_block_mean_k(ctx: Context):
    steps = ctx.steps_in(ctx.window, "decode")
    if not steps:
        return None
    return sum(s.k for s in steps) / len(steps)


def kernel_required_s(ctx: Context) -> float:
    """Least time the paged decode kernel could take for the decode work
    of the traced part: per iteration and layer, the larger of its live
    flops over peak and its live bytes over HBM bandwidth."""
    total = 0.0
    for s in ctx.steps_in(ctx.traced, "decode"):
        for i in range(s.k):
            lens = [pos + i + 1 for pos, emitted in s.rows if emitted > i]
            f, b = counts.decode_attention_work(ctx.dims, lens,
                                                ctx.kv_bytes, ctx.q_bytes)
            total += ctx.dims.layers * max(
                f / ctx.peaks["flops_bf16"], b / ctx.peaks["hbm_bytes_per_s"])
    return total


def kernel_roofline(ctx: Context, kernel: str):
    """Required time over the kernel's device time, in percent."""
    if ctx.summary is None:
        return None
    dev = ctx.summary.op_s.get(kernel, 0.0)
    need = kernel_required_s(ctx)
    if dev <= 0 or need <= 0:
        return None
    return 100.0 * need / dev


def step_mfu(ctx: Context):
    """Required model flops of the tokens processed in the traced part,
    over its length times peak times the devices holding a replica."""
    if ctx.summary is None:
        return None
    flops = 0.0
    for s in ctx.steps_in(ctx.traced):
        if s.kind == "prefill":
            for start, n in s.rows:
                flops += counts.prefill_flops(ctx.dims, start, n, False)
            # the head once for each prompt this step completed
            flops += s.completed * counts.head_flops(ctx.dims)
        elif s.kind == "decode":
            for pos, emitted in s.rows:
                flops += counts.decode_flops(ctx.dims, pos, emitted)
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.summary.window_s * ctx.peaks["flops_bf16"]
                            * ctx.replica_devices)


def device_idle_share(ctx: Context):
    if ctx.summary is None or ctx.summary.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.summary.busy_mean_s() / ctx.summary.window_s)


def tpot_p90_s(ctx: Context):
    xs = stats.tpots(ctx.clients)
    return stats.percentile(xs, 90) if xs else None



def seed_checkpoint_gb(ctx: Context):
    """Bytes of the seed checkpoint that the cluster's set-up asked to
    write to disk, in GB; unknown where it could not be intercepted."""
    if ctx.seed_checkpoint_bytes is None:
        return None
    return ctx.seed_checkpoint_bytes / 1e9
