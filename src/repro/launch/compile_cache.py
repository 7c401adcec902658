"""JAX's persistent compilation cache, kept where the next process finds it.

A cold start of a function image is mostly compile time; with the cache a
later process of the same checkout loads the compiled programs instead.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the only directory used.
    Otherwise the cache lives at ``<checkout>/.jax_cache/``: a fixed path, so
    every process of this checkout shares it. Every compile is cached, however
    short. Call once from an entry point, before the first compile; never at
    import time or from tests.
    """
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
