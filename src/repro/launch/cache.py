"""JAX persistent compilation cache, placeable from outside.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
``enable_compile_cache()`` before any JAX work; importing the library never
touches the cache.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and no directory is set here.  Otherwise the cache lives at the
fixed ``<repo>/.jax_cache``: the path is part of the cache's key, so it is
never built from a temporary directory, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<repo>/.jax_cache``: this file is ``<repo>/src/repro/launch/cache.py``.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program, however small or quick to compile.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
