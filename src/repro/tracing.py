"""Program spans: the served path's layer boundaries in the profiler trace.

A span is a ``jax.profiler.TraceAnnotation`` named
``hfx.<layer>.<phase>``.  It lands in the ``.xplane.pb`` that
``jax.profiler.start_trace`` writes, on the same clock as the device
ops, and only while a profiler runs.  With none running, ``span``
returns one shared no-op context and computes nothing: no span object,
no argument.

Arguments become the event's stats (``ProfileEvent.stats``).  An
argument given as a zero-argument callable is called only while a
profiler runs, so a call site passes anything that costs more than a
read (a join, a sum, a ``len`` over a collection) as a lambda.  A
``None`` value is left out.  The TraceMe encoding splits on ``,`` and
``#``: a string value holds neither (lists of ids are joined with
``;``).
"""

from __future__ import annotations

from contextlib import nullcontext

from jax.profiler import TraceAnnotation

_OFF = nullcontext()


def _values(args: dict) -> dict:
    out = {}
    for k, v in args.items():
        if callable(v):
            v = v()
        if v is not None:
            out[k] = v
    return out


def span(name: str, **args):
    """A context manager for one span; ``with span(...) as sp`` binds
    the live annotation, or None while no profiler runs."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return TraceAnnotation(name, **_values(args))


def annotate(sp, **args) -> None:
    """Add arguments known only inside the span ``sp`` (as bound by
    ``with span(...) as sp``; None is a no-op)."""
    if sp is not None:
        sp.set_metadata(**_values(args))
