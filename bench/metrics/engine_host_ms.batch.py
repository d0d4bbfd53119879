"""Reader of the per-layer metric `engine_host_ms.batch` (see bench/phases.py)."""
from bench.phases import engine_host_ms as read  # noqa: F401
