"""The paged decode kernel's device time against the roofline of the
live work it was given (``harness/counts.py``)."""

from harness.layers import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "paged_decode_attention")
