"""JAX persistent compilation cache for the entry-point scripts.

``chip_smoke.py``, ``examples/*.py`` and ``benchmarks/run.py`` call
:func:`enable_compile_cache` once, before their first compile; library
code never does (importing the package changes no JAX setting).

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set, and
otherwise ``.jax_cache`` in the checkout (git ignores it).  The path is
part of every cache key, so it is fixed: never derived from a temp name,
a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    cache every compile, however short; returns the directory."""
    import jax

    path = os.environ.get(ENV) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
