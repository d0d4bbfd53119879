"""Reader of the per-layer metric `schedule_ms.batch` (see bench/phases.py)."""
from bench.phases import schedule_ms as read  # noqa: F401
