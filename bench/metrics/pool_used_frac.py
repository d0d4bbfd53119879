"""Reader of the per-layer metric `pool_used_frac` (see bench/readings.py)."""
from bench.readings import pool_used_frac as read  # noqa: F401
