"""Find a cell's knee: the highest offered rate that the system sustains.

    python3 perfbench/sweep.py --workload qwen7b.four-task --seed 3 \\
        --seconds 20 --rates 1 1.5 2 2.5 3

One process, one cluster: after the warm-up, each rate in turn is
offered for ``--seconds`` (after the mix's lead) and drained up to the
mix's cap, and one JSON line reports it.  A rate is ``sustained`` when
at least 90% of the requests sent in its window meet both Table 1 SLOs
and every one of them ends within the drain cap; its backlog grows when
more requests wait for a slot at the window's end than at its first
third.  The cells' fixed rates are set from this once, on the chip, and
written into their traffic files; the benchmark itself never searches.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402  (puts this directory on sys.path)


def queued(cluster) -> int:
    """Requests waiting for a slot: in the dispatcher or an engine."""
    return (len(cluster.policy.queued_requests())
            + sum(len(w.waiting) for w in cluster.workers))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()

    import jax

    from harness import serve, stats
    from harness.cell import load_cell
    from harness.traffic import schedule

    cell = load_cell(args.workload)
    jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = run.describe_device()
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        print(f"perfbench sweep: needs {cell.chips} TPU chip(s), JAX sees "
              f"{dev}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(run.ROOT / "src"))
    cluster, _ = serve.build_cluster(cell, args.seed)
    driver = serve.Driver(cell, cluster, trace=False)
    serve.warm_up(driver, cell, args.seed)
    run.emit("setup", setup_s=time.perf_counter() - T_START, device=dev)
    eng = cell.config["engine"]
    for i, rate in enumerate(args.rates):
        mix = {**cell.traffic, "rate_rps": rate}
        reqs = schedule(mix, args.seed + i, args.seconds,
                        cell.config["vocab_size"], eng["max_len"])
        origin = time.perf_counter()
        window = (origin + mix["lead_s"],
                  origin + mix["lead_s"] + args.seconds)
        driver.tokens_in_window = 0
        driver.late = []
        depth = {}

        def on_tick(now, window=window):
            for name, at in (("third", window[0] + args.seconds / 3),
                             ("end", window[1])):
                if name not in depth and now >= at:
                    depth[name] = queued(cluster)

        sent = driver.run(reqs, origin, window,
                          window[1] + max(mix["drain_cap_s"], 15.0), True,
                          on_tick)
        end = time.perf_counter()
        tp = stats.tpots(sent)
        att = stats.attainment(sent)
        unfinished = sum(not driver.done(c) for c in sent)
        run.emit("rate", rate_rps=rate, sent=len(sent),
                 attainment=att,
                 rejected=sum(c.rejected for c in sent),
                 unfinished=unfinished,
                 ttft_p50_s=stats.percentile(stats.ttfts(sent, end), 50),
                 ttft_p90_s=stats.percentile(stats.ttfts(sent, end), 90),
                 tpot_p90_s=stats.percentile(tp, 90) if tp else None,
                 output_tok_s=driver.tokens_in_window / args.seconds,
                 drained_s=end - window[1],
                 queued_at_third=depth.get("third"),
                 queued_at_end=depth.get("end"),
                 sustained=bool(att >= 0.9 and unfinished == 0),
                 backlog_grows=bool(depth.get("end", 0)
                                    > depth.get("third", 0) + 2))
        # let the queue empty before the next rate
        driver.drain()
    return 0


if __name__ == "__main__":
    sys.exit(main())
