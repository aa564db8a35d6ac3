"""Host time per engine step in cluster event handling outside
``EngineWorker.run_step`` (``serving/cluster.py``, ``core/``)."""

from harness.layers import control_ms_per_step as read  # noqa: F401
