"""Persistent XLA compilation cache for the entry-point scripts.

The vector engine compiles one executable per shape family, and on a TPU
each one takes minutes, so scripts keep compiled programs on disk. The
library never turns the cache on by itself: ``chip_smoke.py``, the
``benchmarks/`` CLIs and ``examples/`` call :func:`enable_compile_cache`
first thing.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR``, when set. JAX reads that variable
  itself, so nothing is set in code.
* Otherwise ``<checkout>/.jax_cache`` (git-ignored). The path is part of
  a cache entry's key, so it is fixed: never a temp name, pid or time.
"""
from __future__ import annotations

import os

#: the fixed in-checkout cache directory (``src/repro/`` -> checkout root)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or CHECKOUT_CACHE_DIR


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
