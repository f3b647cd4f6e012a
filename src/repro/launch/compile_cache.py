"""Where JAX keeps its persistent compilation cache.

`enable_compile_cache()` is for the `main()` of an entry point, never for
import time. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its
own and nothing is set here. Otherwise the cache goes to the fixed,
git-ignored ``<repo>/.jax_cache``: the directory takes part in the cache
key, so it never moves between runs (no temporary, pid- or time-based path).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
