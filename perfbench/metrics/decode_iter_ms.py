"""Wall time of the engine's decode dispatches over their iterations."""

from harness.layers import decode_iter_ms as read  # noqa: F401
