"""Where the persistent XLA compile cache lives.

The flagship train step takes most of a minute to compile for a TPU and
every serving engine compiles a prefill ladder, so the measurement entry
points (``chip_smoke.py``, ``tools/bench_configs.py``,
``tools/bench_tf_import.py``) call :func:`enable_compile_cache` before
their first compile. The library never turns the cache on by itself, and
the tests leave it off.
"""
from __future__ import annotations

import os

import jax

# The directory is part of the cache key, so it must not move between
# runs: a fixed path inside the checkout, never a temp name or a pid.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing; otherwise the cache goes to ``.jax_cache`` at the
    root of the checkout (git-ignored)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
