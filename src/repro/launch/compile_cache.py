"""JAX's persistent compilation cache, kept in one fixed place."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# the checkout root: src/repro/launch/compile_cache.py -> three levels up
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here. Otherwise the cache is ``.jax_cache/`` at
    the checkout root — a fixed path, because the path is part of what a
    cache entry is found under. Call it before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
