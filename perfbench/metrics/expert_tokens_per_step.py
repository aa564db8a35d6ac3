"""Mean assignments each held expert computes per decode iteration and
layer: the decode steps' ``expert_tokens`` (``hfx.engine.step``, summed
over layers and fused iterations) over their iterations, the held
experts and the expert layers.  None where the program's steps carry no
such count."""

from harness import moe_counts, program_spans


def read(ctx):
    if ctx.summary is None:
        return None
    steps = [s.args for s in program_spans.load().spans
             if s.name == "hfx.engine.step" and s.args.get("kind") == "decode"
             and "expert_tokens" in s.args]
    if not steps:
        return None
    m = moe_counts.MoeDims.of(ctx.cell.config)
    iters = sum(int(a.get("k", 1)) for a in steps)
    return (sum(int(a["expert_tokens"]) for a in steps)
            / (iters * m.held * m.moe_layers))
