"""Reader of the per-layer metric `decode_batch_mean` (see bench/readings.py)."""
from bench.readings import decode_batch_mean as read  # noqa: F401
