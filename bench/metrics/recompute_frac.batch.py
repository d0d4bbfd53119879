"""Reader of the per-layer metric `recompute_frac.batch` (see bench/phases.py)."""
from bench.phases import recompute_frac as read  # noqa: F401
