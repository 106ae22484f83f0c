"""Persistent XLA compile cache for the entry scripts.

Only entry scripts call :func:`enable_compile_cache` (``chip_smoke.py``
and ``benchmark/run.py``); library modules never set a cache.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
directory is set here.  Otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``): the path is part of
the cache key, so a temp, pid or time path would never hit.
"""

from __future__ import annotations

import os

__all__ = ["enable_compile_cache"]

#: the checkout root: the directory holding the ``geomesa_tpu`` package
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(CHECKOUT, ".jax_cache"))
    # cache every program: the smoke path compiles dozens of sub-second
    # programs whose sum dominates a cold run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir
