"""JAX's persistent compilation cache, in one place for every entry point.

A cold chip run compiles every solve, kernel and bucket again; the
persistent cache lets a second process (or a second run on the same
machine) read them back.  A later run finds the cache only where this one
left it, so the directory is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the
environment sets
it (JAX reads that variable itself, and nothing here overrides it), else
``<repo>/.jax_cache``.  Entry points call :func:`enable_compile_cache` once,
before their first compile; library code and tests never do.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the cache directory when the environment names none
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
