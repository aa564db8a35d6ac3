"""Record the small chip trace that ``test_perfbench_trace.py`` reads.

    python3 perfbench/tests/record_trace.py perfbench/tests/data/trace

On a TPU: three "engine steps", each a jitted matmul and one call of
the program's paged decode kernel, inside the benchmark's host spans,
with a 5 ms sleep before each step as an idle gap.
"""

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.decode_attention import paged_decode_attention  # noqa: E402


def main(out: str) -> None:
    if jax.default_backend() != "tpu":
        sys.exit("record_trace: needs a TPU")
    b, hq, hkv, ps, d, mp = 2, 4, 2, 16, 128, 8
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
    kp = jax.random.normal(ks[1], (b * mp, hkv, ps, d), jnp.float32)
    vp = jax.random.normal(ks[2], (b * mp, hkv, ps, d), jnp.float32)
    table = jnp.arange(b * mp, dtype=jnp.int32).reshape(b, mp)
    lens = jnp.array([40, 100], jnp.int32)
    x = jax.random.normal(ks[3], (1024, 1024), jnp.float32)
    mm = jax.jit(lambda a: jnp.tanh(a @ a))
    jax.block_until_ready((mm(x), paged_decode_attention(q, kp, vp, table,
                                                         lens)))
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("perfbench.window"):
        # the trace's device clock may run a millisecond apart from the
        # host's: start with an idle stretch so no step straddles the edge
        with jax.profiler.TraceAnnotation("perfbench.idle"):
            time.sleep(0.005)
        for _ in range(3):
            with jax.profiler.TraceAnnotation("perfbench.engine_step"):
                jax.block_until_ready(
                    (mm(x), paged_decode_attention(q, kp, vp, table, lens)))
            with jax.profiler.TraceAnnotation("perfbench.idle"):
                time.sleep(0.005)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
