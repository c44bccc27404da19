"""Where compiled programs are kept between processes.

JAX's persistent compilation cache keys an entry partly by the cache's own
path, so a directory that moves (a temp name, a pid, a timestamp) never
hits.  :func:`enable_compile_cache` is the one place the entry points
(``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks/run.py``) turn the
cache on.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE", "enable_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, that directory is the
    cache: JAX reads the variable itself, so nothing is set here.
    Otherwise the cache is the fixed ``<checkout>/.jax_cache``
    (gitignored)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
