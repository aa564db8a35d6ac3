"""Benchmark harness entry point — one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig3,table2]
        [--json [PATH]]

Prints ``name,us_per_call,derived`` CSV.  ``--json`` additionally
collects the machine-readable payloads some benches attach to their
rows (currently ``decode_block``: tokens/s, dispatches per token,
block-size histogram) into a JSON file — ``BENCH_decode.json`` by
default — which CI uploads as the perf-trajectory artifact.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


BENCHES = [
    ("fig3_multitask", "benchmarks.bench_multitask"),
    ("fig4_pd_disagg", "benchmarks.bench_pd_disagg"),
    ("fig5_priority_mapping", "benchmarks.bench_priority_mapping"),
    ("table2_fast_scaling", "benchmarks.bench_fast_scaling"),
    ("fig6_dynamic_slo", "benchmarks.bench_dynamic_slo"),
    ("fig7_single_task", "benchmarks.bench_single_task"),
    ("fig8_intervals", "benchmarks.bench_intervals"),
    ("appA_latency_model", "benchmarks.bench_latency_model"),
    ("kernels", "benchmarks.bench_kernels"),
    ("roofline", "benchmarks.bench_roofline"),
    ("chunked_prefill", "benchmarks.bench_chunked_prefill"),
    ("decode_block", "benchmarks.bench_decode_block"),
    ("spec_decode", "benchmarks.bench_spec_decode"),
    ("online_streaming", "benchmarks.bench_online_streaming"),
    ("prefix_cache", "benchmarks.bench_prefix_cache"),
    ("live_migration", "benchmarks.bench_live_migration"),
    ("fault_recovery", "benchmarks.bench_fault_recovery"),
]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sample counts (slow)")
    ap.add_argument("--only", default="",
                    help="comma-separated benchmark name filter")
    ap.add_argument("--json", nargs="?", const="BENCH_decode.json",
                    default=None, metavar="PATH",
                    help="write machine-readable rows (benches that "
                         "attach them) to PATH [BENCH_decode.json]")
    args = ap.parse_args()
    only = [s for s in args.only.split(",") if s]

    print("name,us_per_call,derived")
    failures = 0
    json_rows: list[dict] = []
    for name, module in BENCHES:
        if only and not any(o in name for o in only):
            continue
        t0 = time.time()
        try:
            mod = __import__(module, fromlist=["run"])
            rows = mod.run(quick=not args.full)
            for r in rows:
                derived = str(r["derived"]).replace(",", ";")
                print(f"{r['name']},{r['us_per_call']},{derived}",
                      flush=True)
                if "json" in r:
                    json_rows.append(r["json"])
            print(f"# {name} done in {time.time()-t0:.1f}s",
                  file=sys.stderr, flush=True)
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{name},0,ERROR {type(e).__name__}: {e}", flush=True)
            import traceback
            traceback.print_exc(file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(json_rows, f, indent=1)
        print(f"# wrote {len(json_rows)} rows to {args.json}",
              file=sys.stderr, flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
