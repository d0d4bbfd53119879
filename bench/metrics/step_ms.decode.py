"""Reader of the per-layer metric `step_ms.decode` (see bench/readings.py)."""
from bench.readings import step_ms_decode as read  # noqa: F401
