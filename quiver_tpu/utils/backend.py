"""The persistent compilation cache, placed from outside.

A products-scale program takes minutes to compile and every process starts
cold, so entry points (``chip_smoke.py``, the examples, the benchmarks)
call :func:`enable_compile_cache` before their first compile. The cache
directory is part of the cache key: it is a fixed path, never a temporary
one.
"""

from __future__ import annotations

import os

__all__ = ["CHECKOUT", "enable_compile_cache"]

# the checkout this package was imported from; the caches that decide
# start-up cost (compile, serving AOT) default under it
CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this
    sets nothing. Otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    cache_dir = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
