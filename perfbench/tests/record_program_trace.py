"""Record the small chip trace that ``test_perfbench_program_spans.py`` reads.

    python3 perfbench/tests/record_program_trace.py \\
        perfbench/tests/data/program_trace

On a TPU: a one-layer model of qwen7b's kind (head_dim 128, the smoke
config's other sizes) serves three requests through the program's
served path (``ServingSession`` -> ``Cluster`` -> ``InferenceEngine``)
with the profiler on, inside ``perfbench.window``.  The engine takes two
prompts at a time, so the third one queues.  An untraced round of the
same requests compiles every program first, and a 5 ms sleep opens and
closes the window so that no step straddles its edge on either clock.
The profiler records no Python calls and no HLO, which keeps the file
small; spans, runtime events and device ops are as in a traced run.
"""

import dataclasses
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_smoke_config  # noqa: E402
from repro.core.request import Request  # noqa: E402
from repro.serving.cluster import Cluster, ClusterConfig  # noqa: E402
from repro.serving.engine import EngineConfig  # noqa: E402
from repro.serving.session import ServingSession  # noqa: E402

PROMPTS = (12, 20, 8)       # tokens; 16-token chunks
L_OUT = (3, 2, 5)


def serve(session: ServingSession, rid0: int) -> None:
    rng = np.random.default_rng(0)
    for i, (n, l_out) in enumerate(zip(PROMPTS, L_OUT)):
        session.submit_request(Request(
            rid=rid0 + i, task="gsm8k", l_in=n, l_out=l_out,
            prompt=rng.integers(1, 256, n).astype(np.int32),
            ttft_slo=5.0, tpot_slo=1.0))
    session.drain()


def record(out: str) -> None:
    model = dataclasses.replace(get_smoke_config("qwen7b"), n_layers=1,
                                d_model=256, n_heads=2, n_kv_heads=2,
                                d_ff=512)
    session = ServingSession(Cluster(ClusterConfig(
        model=model, backend="engine", n_workers=1, policy="hyperflexis",
        seed=0, engine=EngineConfig.smoke(decode_block=4))),
        admission="none")
    serve(session, 0)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("perfbench.window"):
        time.sleep(0.005)
        serve(session, 100)
        time.sleep(0.005)
    jax.profiler.stop_trace()
    session.close()


def main(out: str) -> None:
    if jax.default_backend() != "tpu":
        sys.exit("record_program_trace: needs a TPU")
    record(out)


if __name__ == "__main__":
    main(sys.argv[1])
