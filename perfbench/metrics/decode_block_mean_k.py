"""Mean decode iterations fused per dispatch (K of ``decode_block``)."""

from harness.layers import decode_block_mean_k as read  # noqa: F401
