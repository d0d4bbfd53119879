"""Reader of the per-layer metric `device_idle_frac.batch` (see bench/readings.py)."""
from bench.readings import device_idle_frac as read  # noqa: F401
