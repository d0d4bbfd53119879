"""Reader of the per-layer metric `mfu.batch` (see bench/readings.py)."""
from bench.readings import mfu as read  # noqa: F401
