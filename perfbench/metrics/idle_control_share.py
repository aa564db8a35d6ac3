"""Share of the traced part in which the device ran no op while the
host was in the control plane: ``hfx.session.*``, ``hfx.cluster.*``,
``hfx.dispatch.*`` (``harness/program_spans.py``)."""

from harness import program_spans


def read(ctx):
    return program_spans.idle_share(ctx, "control")
