"""The chip benchmark: one cell of BENCHMARK.json through the served path.

    python3 perfbench/run.py --workload qwen7b.four-task --seed 7 \\
        --seconds 40 --trace 0

Runs from the root of a checkout that holds the program (``src/``) and
this directory, on a machine with the chips the cell asks for.  With no
TPU, or fewer chips than the cell asks for, it exits non-zero before
serving and prints no result.  The last line of standard output is the
result: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``; last comes
``check``, each compared number beside its limit, which also ends
standard error.  Earlier lines are JSON records of each phase.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = BENCH / ".trace"


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def describe_device() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def end_to_end(name: str, clients, seconds, tokens, end):
    from harness import stats

    if name == "ttft_p90_s":
        return stats.percentile(stats.ttfts(clients, end), 90)
    if name == "tpot_p90_s":
        xs = stats.tpots(clients)
        return stats.percentile(xs, 90) if xs else None
    if name == "slo_attainment":
        return stats.attainment(clients)
    if name == "output_tok_s":
        return tokens / seconds
    raise KeyError(f"no end-to-end metric {name!r} in the harness")


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_start: float, control: bool = False) -> dict:
    """Serve one run of ``cell`` and judge it; returns the result line.
    ``control`` also reads the fp8 control's gap (``control.py``)."""
    import gc

    import jax
    import numpy as np

    sys.path.insert(0, str(ROOT / "src"))

    from harness import check, counts, serve
    from harness.cell import read_per_layer
    from harness.layers import Context
    from harness.peaks import peaks
    from harness.traffic import schedule

    dev = describe_device()
    mix, eng_cfg = cell.traffic, cell.config["engine"]
    monitor = serve.Monitor()
    t_cluster = time.perf_counter()
    cluster, checkpoint_bytes = serve.build_cluster(cell, seed)
    driver = serve.Driver(cell, cluster, trace=trace)
    t_warm = time.perf_counter()
    serve.warm_up(driver, cell, seed)
    driver.longest = [(0.0, None, 0.0)] * 3
    reqs = schedule(mix, seed, seconds, cell.config["vocab_size"],
                    eng_cfg["max_len"])

    origin = time.perf_counter()
    setup_s = origin - t_start
    window = (origin + mix["lead_s"], origin + mix["lead_s"] + seconds)
    drain = mix["drain_cap_s"] > 0
    stop = window[1] + mix["drain_cap_s"]
    traced = (window[0] + 0.25 * seconds,
              window[0] + 0.25 * seconds + min(6.0, 0.5 * seconds))
    tracer = {}

    def on_tick(now):
        if not trace:
            return
        if "on" not in tracer and now >= traced[0]:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            tracer["span"] = jax.profiler.TraceAnnotation("perfbench.window")
            tracer["span"].__enter__()
            tracer["on"] = time.perf_counter()
        elif "on" in tracer and "off" not in tracer and now >= traced[1]:
            tracer["off"] = time.perf_counter()
            tracer["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()

    emit("setup", setup_s=setup_s, before_cluster_s=t_cluster - t_start,
         cluster_s=t_warm - t_cluster, warm_up_s=origin - t_warm,
         seed_checkpoint_skipped_bytes=checkpoint_bytes,
         requests=len(reqs),
         in_window=sum(r.in_window for r in reqs), device=dev)
    pauses = []

    def gc_pause(phase, info, t={}):
        if phase == "start":
            t["start"] = time.perf_counter()
        elif "start" in t:
            pauses.append(time.perf_counter() - t.pop("start"))

    gc.callbacks.append(gc_pause)
    monitor.armed = True
    sent = driver.run(reqs, origin, window, stop if drain else window[1],
                      drain, on_tick)
    end = time.perf_counter()
    monitor.armed = False
    gc.callbacks.remove(gc_pause)
    if "on" in tracer and "off" not in tracer:
        tracer["off"] = time.perf_counter()
        tracer["span"].__exit__(None, None, None)
        jax.profiler.stop_trace()

    n_rej = sum(c.rejected for c in sent)
    n_fail = sum(c.failed for c in sent)
    n_open = sum(not driver.done(c) for c in sent)
    late = np.asarray(driver.late) if driver.late else np.zeros(1)
    emit("window", seconds=seconds, sent=len(sent),
         finished=sum(c.finished for c in sent), rejected=n_rej,
         failed=n_fail, unfinished=n_open,
         late_p50_ms=1e3 * float(np.percentile(late, 50)),
         late_p99_ms=1e3 * float(np.percentile(late, 99)),
         compiles_in_window=monitor.row(),
         tokens_in_window=driver.tokens_in_window,
         served_for_s=end - origin,
         longest_events=[[k, dt, t - origin] for dt, k, t in driver.longest],
         gc_pauses=len(pauses), gc_longest_s=max(pauses, default=0.0))
    peak = serve.peak_bytes()
    device = {**dev, "memory_peak_bytes": peak}
    emit("memory", peak_bytes=peak)

    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                value = setup_s
            else:
                value = end_to_end(m["name"], sent, seconds,
                                   driver.tokens_in_window, end)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # everything the window needs from the program is now in hand
    worker = cluster.workers[-1].engine
    kv_leaf = jax.tree.leaves(worker.caches)[0]
    kv_bytes, q_bytes = kv_leaf.dtype.itemsize, kv_leaf.dtype.itemsize
    replica_devices = len({d for w in cluster.workers
                           for leaf in jax.tree.leaves(w.engine.params)
                           for d in leaf.devices()})
    steps, events = driver.steps, driver.events
    picked = check.sample(sent, seed, cell.config["check"]["sample_tokens"])
    del driver, cluster, worker, kv_leaf
    gc.collect()
    emit("freed", live_bytes=sum(x.nbytes for x in jax.live_arrays()))

    t_check = time.perf_counter()
    weights = serve.make_weights(cell, seed, program=False)
    readings = check.gaps(cell, weights, picked, control=control)
    del weights
    limit = cell.config["check"]["max_logit_gap"]
    correct = bool(picked) and readings["max_logit_gap"] <= limit
    emit("check", **readings, limit=limit,
         seconds=time.perf_counter() - t_check)

    out = {"correct": correct, "attempted": len(sent),
           "failed": n_rej + n_fail + (n_open if drain else 0),
           "metrics": metrics, "device": device}
    if trace:
        from harness import trace as tr

        summary = tr.summarize(tr.load(TRACE_DIR))
        ctx = Context(
            cell=cell, window=window,
            traced=(tracer["on"], tracer["off"]), steps=steps,
            events=events, clients=sent, summary=summary,
            dims=counts.Dims.of(cell.config), kv_bytes=kv_bytes,
            q_bytes=q_bytes, peaks=peaks(dev["kind"]),
            replica_devices=replica_devices,
            seed_checkpoint_bytes=checkpoint_bytes)
        out["metrics"] = read_per_layer(cell, ctx)
        device["busy_s"] = summary.busy_mean_s()
        device["window_s"] = summary.window_s
        emit("trace", busy_s=summary.busy_s, window_s=summary.window_s,
             op_calls=summary.op_calls, idle_by_span=summary.idle_by_span())
        out["breakdown"] = summary.breakdown()
    out["check"] = {"max_logit_gap": {"value": readings["max_logit_gap"],
                                      "limit": limit}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness.cell import load_cell

    cell = load_cell(args.workload)
    import jax

    # one fixed directory inside the checkout, so that only a cell's
    # first run there compiles; small programs are kept too
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = describe_device()
    if dev["platform"] != "tpu":
        print(f"perfbench: JAX found no TPU (platform {dev['platform']!r});"
              f" the benchmark runs only on the chip", file=sys.stderr)
        return 2
    if dev["count"] < cell.chips:
        print(f"perfbench: {args.workload} needs {cell.chips} chips, JAX "
              f"sees {dev['count']}", file=sys.stderr)
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START)
    for name, c in out["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
