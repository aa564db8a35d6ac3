"""Readings that the correctness limit is set from, on the chip.

    python3 perfbench/control.py --workload qwen7b.four-task \\
        --seconds 15 --seeds 101 102 103

For each seed, in one process: a run of the cell as the benchmark makes
it (set-up, the open-loop window at the cell's own rate, the sample of
finished requests), then, on the same prompts and served tokens, the
reference's widest gap for the served tokens (the program's reading)
and for the tokens that the reference computed in fp8 puts first (the
control's reading).  One JSON line per seed.  The limit in the
configuration file lies between the largest program reading and the
smallest control reading; ``PERF.md`` gives both.
"""

from __future__ import annotations

import argparse
import gc
import sys
import time

import run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()

    import jax

    from harness.cell import load_cell

    cell = load_cell(args.workload)
    jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = run.describe_device()
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        print(f"perfbench control: needs {cell.chips} TPU chip(s), JAX "
              f"sees {dev}", file=sys.stderr)
        return 2
    for seed in args.seeds:
        run.run_cell(cell, seed, args.seconds, False, time.perf_counter(),
                     control=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
