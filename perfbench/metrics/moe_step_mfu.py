"""Required flops of the DeepSeek-V3 block's active work in the traced
part, over its length times the chip's bf16 peak times the replicas'
chips: latent attention (absorbed) at live context, the router, shared
experts and dense layer of every token processed, the held experts'
assignments (the steps' ``expert_tokens``), and the head once per
emitted token (``harness/moe_counts.py``).  None where the program's
steps carry no expert count."""

from harness import moe_counts, program_spans


def read(ctx):
    if ctx.summary is None:
        return None
    assigned = [int(s.args["expert_tokens"])
                for s in program_spans.load().spans
                if s.name == "hfx.engine.step" and "expert_tokens" in s.args]
    if not assigned:
        return None
    m = moe_counts.MoeDims.of(ctx.cell.config)
    flops = moe_counts.expert_work(m, sum(assigned), 0)[0]
    for s in ctx.steps_in(ctx.traced):
        if s.kind == "prefill":
            for start, n in s.rows:
                flops += moe_counts.prefill_flops(m, start, n)
            flops += s.completed * moe_counts.head_flops(m)
        elif s.kind == "decode":
            for pos, emitted in s.rows:
                flops += moe_counts.decode_flops(m, pos, emitted)
    return 100.0 * flops / (ctx.summary.window_s * ctx.peaks["flops_bf16"]
                            * ctx.replica_devices)
