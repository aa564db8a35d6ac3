"""The 90th percentile of time per output token, from the client's
stamps, where it is not an end-to-end metric: above capacity, where
the tail follows the queue more than the program."""

from harness.layers import tpot_p90_s as read  # noqa: F401
