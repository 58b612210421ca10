"""Persistent JAX compilation cache for the repository's entry points.

Called at start-up by ``chip_smoke.py``, ``examples/serve_ann.py`` and
``benchmarks/run.py`` (never at package import, so tests are unaffected).
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing is set here; otherwise the cache goes to ``.jax_cache`` at the
repository root — a fixed path, since the path is part of the cache key.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
