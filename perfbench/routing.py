"""How often the program's experts differ from the reference's, on the
positions the check scores.

    python3 perfbench/routing.py --workload moonlight-16b-a3b.chat-math-sat \\
        --seconds 40 --seeds 101 102

For each seed, in one process: a run of the cell as ``control.py`` makes
it (its ``check`` record carries the program's widest gap and the fp8
control's), then, over the sample of finished requests that the check
scores, the top-k experts that each expert layer chose at each served
position: by the reference (``routes``: float32 at HIGHEST) and by the
program's own forward pass (``Model.forward`` at the chip's default
precision, its router at HIGHEST) on the same weights.  One JSON record
per seed: the (position, layer) pairs compared, and in how many the
chosen sets differ.  The forward pass is the program's code but not the
served path's schedule (chunks, fused decode), whose rounding may
differ again.
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

import run


def flips(cell, weights, requests) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.models import build_model, moe

    ref, adapter = cell.reference(), cell.adapter()
    length = cell.config["engine"]["max_len"]
    ref_routes = jax.jit(lambda w, t: ref.routes(w, cell.config, t))
    seen: list = []
    route = moe.route

    def recording(params, xt, cfg):
        probs, sel, w = route(params, xt, cfg)
        jax.debug.callback(lambda s: seen.append(np.asarray(s)), sel,
                           ordered=True)
        return probs, sel, w

    moe.route = recording
    try:
        model = build_model(adapter.model_config(cell.config))
        params = adapter.program_params(weights)   # shares the experts
        forward = jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0, 0])
        n_pairs = n_diff = 0
        for r in requests:
            seq = np.concatenate([np.asarray(r.prompt[: r.l_in], np.int32),
                                  np.asarray(r.generated, np.int32)])
            tokens = np.zeros(length, np.int32)
            tokens[: len(seq) - 1] = seq[:-1]
            want = np.asarray(ref_routes(weights, jnp.asarray(tokens)))
            seen.clear()
            jax.block_until_ready(forward(params, jnp.asarray(tokens)[None]))
            jax.effects_barrier()
            got = np.stack(seen)            # (expert layers, S, k)
            rows = slice(r.l_in - 1, len(seq) - 1)
            a = np.sort(got[:, rows], -1)
            b = np.sort(want[:, rows], -1)
            n_pairs += a.shape[0] * a.shape[1]
            n_diff += int(np.sum(np.any(a != b, -1)))
    finally:
        moe.route = route
    return {"pairs": n_pairs, "differ": n_diff}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()

    import jax

    from harness import check
    from harness.cell import load_cell

    cell = load_cell(args.workload)
    jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = run.describe_device()
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        print(f"perfbench routing: needs {cell.chips} TPU chip(s), JAX "
              f"sees {dev}", file=sys.stderr)
        return 2
    gaps = check.gaps

    def gaps_and_flips(cell_, weights, requests, **kw):
        out = gaps(cell_, weights, requests, **kw)
        run.emit("routing", **flips(cell_, weights, requests))
        return out

    check.gaps = gaps_and_flips
    for seed in args.seeds:
        run.run_cell(cell, seed, args.seconds, False, time.perf_counter(),
                     control=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
