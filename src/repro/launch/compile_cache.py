"""Where the entry points keep JAX's persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set, nothing
here overrides it. Otherwise the entry points (``chip_smoke.py``,
``repro.launch.discover``, ``benchmarks.run``) point JAX at the fixed
``<checkout>/.jax_cache``. The directory is part of what lets a later run
find an entry, so it is never built from a temporary name, a pid or the
time. Only entry points call this — importing ``repro`` leaves the cache
off, so the test suite compiles uncached.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def configure_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
