"""Persistent JAX compilation cache for the entry points of the served path.

``JAX_COMPILATION_CACHE_DIR``, when set, places the cache: JAX reads the
variable itself and this module points nowhere else. Unset, the cache lives
in one fixed directory of the checkout, ``.jax_cache/`` (git-ignored). The
directory is part of what a later run must find again, so it is never built
from a temporary name, a process id or the time.

Call :func:`enable_compile_cache` before the process compiles anything: JAX
decides once per process, at its first compile, whether a cache is in use.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # the engine's ingest, merge and query programs each compile in well
    # under JAX's default 1 s floor, below which nothing would be written
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
