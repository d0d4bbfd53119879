"""Persistent XLA compilation cache for the repo's entry points.

Called from ``main()``s and ``chip_smoke.py``, never when a module is
imported. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing is set here; otherwise the cache lives at one fixed path in the
checkout (``.jax_cache``, git-ignored): the path is part of the cache key,
so a per-run temp directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
