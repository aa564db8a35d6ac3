"""Share of the traced part in which no op ran on the device."""

from harness.layers import device_idle_share as read  # noqa: F401
