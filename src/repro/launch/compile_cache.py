"""JAX's persistent compilation cache, in one place a rerun finds again.

Entry points call `enable_compile_cache()` before their first compile. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and nothing
is set here. Otherwise the cache lives at the fixed ``<repo>/.jax_cache``:
a directory named after a temporary path, a pid or the time would never be
found by the next run.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns that path."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
