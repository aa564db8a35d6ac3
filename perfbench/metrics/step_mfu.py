"""Required model flops of the tokens processed in the traced part,
over its length times the chip's bf16 peak times the replicas' chips."""

from harness.layers import step_mfu as read  # noqa: F401
