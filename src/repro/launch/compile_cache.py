"""JAX's persistent compilation cache for the repository's entry points.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` at the start of
``main``; importing a library module never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the fixed fallback location; the cache is keyed by its path, so a
#: directory that moved between runs would never hit
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache lives in ``<repo>/.jax_cache``.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
