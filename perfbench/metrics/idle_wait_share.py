"""Share of the traced part in which the device ran no op while the
host's innermost program span was ``hfx.engine.wait``: the host blocked
on a chip with nothing to run (``harness/program_spans.py``)."""

from harness import program_spans


def read(ctx):
    return program_spans.idle_share(ctx, "wait")
