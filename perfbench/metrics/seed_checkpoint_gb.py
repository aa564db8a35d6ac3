"""The seed checkpoint that ``WeightManager`` writes at every cluster
build, kept from disk by the harness and so left out of ``setup_s``."""

from harness.layers import seed_checkpoint_gb as read  # noqa: F401
