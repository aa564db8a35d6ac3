"""Mean wall time of the engine steps that carry a prefill chunk."""

from harness.layers import prefill_chunk_ms as read  # noqa: F401
