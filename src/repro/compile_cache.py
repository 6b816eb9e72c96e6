"""Persistent XLA compilation cache at a fixed place.

A cold process on the chip compiles every bitstream again; JAX's
persistent cache lets the next process load them instead.  The cache
directory is part of what makes an entry findable, so it must not move
between runs: it is never built from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os

# <checkout>/.jax_cache (this file is <checkout>/src/repro/compile_cache.py)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it (and its
    other ``JAX_PERSISTENT_CACHE_*`` settings) itself and nothing is set
    here; otherwise the cache goes to ``<checkout>/.jax_cache`` and keeps
    every compile, however short."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    # every bitstream counts: the region programs are many and small, and
    # most compile in under JAX's default one-second floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return CHECKOUT_CACHE_DIR
