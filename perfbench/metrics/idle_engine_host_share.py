"""Share of the traced part in which the device ran no op while the
host was in the engine's own work: ``hfx.engine.step`` outside its
phases, ``plan``, ``launch``, ``commit``, and ``hfx.kv.*``
(``harness/program_spans.py``)."""

from harness import program_spans


def read(ctx):
    return program_spans.idle_share(ctx, "engine")
