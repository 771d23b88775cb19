"""JAX's persistent compilation cache for the programs this repo launches.

A chip run compiles every step program before it serves a token; keeping the
compiled programs on disk lets the next run of the same code load them.  The
cache key includes the directory, so the directory is fixed: never built
from a temporary name, a process id or the time.

Call ``enable_compile_cache()`` from an entry point's ``main``, never on
import and never from tests.
"""
from __future__ import annotations

import os

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` where that is set (JAX already reads it,
    so nothing else is set), else ``<checkout>/.jax_cache``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
