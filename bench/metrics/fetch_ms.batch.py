"""Reader of the per-layer metric `fetch_ms.batch` (see bench/phases.py)."""
from bench.phases import fetch_ms as read  # noqa: F401
