"""The routed experts' grouped matmuls (the ``ragged-dot`` ops of the
device trace) against the roofline of the work the steps' spans count:
for each step the larger of its assignments' flops over peak and the
hit experts' weight bytes over HBM bandwidth (``harness/moe_counts.py``).
None where the program's steps carry no such count."""

from harness import moe_counts, program_spans

OPS = "ragged-dot"


def read(ctx):
    if ctx.summary is None:
        return None
    dev = sum(s for k, s in ctx.summary.op_s.items() if k.startswith(OPS))
    m = moe_counts.MoeDims.of(ctx.cell.config)
    need = 0.0
    for s in program_spans.load().spans:
        if s.name != "hfx.engine.step" or "expert_tokens" not in s.args:
            continue
        f, b = moe_counts.expert_work(m, int(s.args["expert_tokens"]),
                                      int(s.args["experts_hit"]))
        need += max(f / ctx.peaks["flops_bf16"],
                    b / ctx.peaks["hbm_bytes_per_s"])
    if dev <= 0 or need <= 0:
        return None
    return 100.0 * need / dev
