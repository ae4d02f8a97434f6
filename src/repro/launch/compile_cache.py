"""JAX's persistent compilation cache, placed where every run finds it.

A cold TPU run spends much of its time compiling.  The cache directory is
part of each entry's key, so it must not move between runs: where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it; otherwise the cache is ``.jax_cache/`` at the repository
root (listed in ``.gitignore``).  Call ``enable()`` before the first
compile of the process.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
