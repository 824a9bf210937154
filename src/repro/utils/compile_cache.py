"""JAX's persistent compilation cache, for the repo's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module leaves the cache alone. Otherwise the cache goes to ``.jax_cache/``
at the root of the checkout: a fixed path, since the cache key includes
it, so every process started from one checkout shares what any of them
compiled. Call ``use_compile_cache()`` before the first compile.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at the checkout's
    ``.jax_cache/`` unless ``JAX_COMPILATION_CACHE_DIR`` already names one.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
