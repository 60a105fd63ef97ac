"""JAX persistent compilation cache, one policy for every entry point.

The cache key includes the directory, so the directory must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
itself, so nothing is overridden here), else the fixed ``.jax_cache`` at the
root of the checkout.  A second run of the same program in the same checkout
then reads what the first one compiled.
"""

from __future__ import annotations

import os

import jax

# src/repro/launch/compile_cache.py -> checkout root
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache for this process and return
    its directory.  Call before the first compilation."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # the serving kernels compile in well under JAX's default 1 s threshold;
    # cache every program so a warm run skips all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
