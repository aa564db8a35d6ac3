"""The paged MLA decode kernel's device time against the roofline of the
live latent work it was given (``harness/moe_counts.py``): per decode
iteration and layer, the larger of its flops over peak and its bytes
over HBM bandwidth."""

from harness import moe_counts

KERNEL = "paged_mla_decode_attention"


def read(ctx):
    if ctx.summary is None:
        return None
    dev = ctx.summary.op_s.get(KERNEL, 0.0)
    m = moe_counts.MoeDims.of(ctx.cell.config)
    need = 0.0
    for s in ctx.steps_in(ctx.traced, "decode"):
        for i in range(s.k):
            lens = [pos + i + 1 for pos, emitted in s.rows if emitted > i]
            f, b = moe_counts.mla_decode_work(m, lens, ctx.kv_bytes,
                                              ctx.q_bytes)
            need += m.layers * max(f / ctx.peaks["flops_bf16"],
                                   b / ctx.peaks["hbm_bytes_per_s"])
    if dev <= 0 or need <= 0:
        return None
    return 100.0 * need / dev
