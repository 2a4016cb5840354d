"""JAX persistent compilation cache, placed for the entry points.

Called by the entry points (``chip_smoke.py``, ``repro.launch.search``,
``benchmarks.run``) before their first compile, never at import, so the
test suite stays cache-free.  Every process of one checkout shares the
cache: the first run pays the XLA compiles and later runs load them.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, already places the cache (JAX
    reads it at import) and nothing else is set here.  Otherwise the cache
    lives at the fixed ``<checkout>/.jax_cache``: the path is part of the
    cache key, so it never depends on a pid, a temp name or a time."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
