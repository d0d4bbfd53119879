"""Reader of the per-layer metric `host_ms_per_step.batch` (see bench/readings.py)."""
from bench.readings import host_ms_per_step as read  # noqa: F401
