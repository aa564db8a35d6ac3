"""Mean wait of a request from the end of its ``hfx.session.submit`` to
the launch of its first prefill chunk, over the requests of the traced
part (``harness/program_spans.py``)."""

from harness.program_spans import queue_wait_ms as read  # noqa: F401
