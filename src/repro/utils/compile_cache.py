"""JAX persistent compilation cache location.

Entry points call :func:`enable_compile_cache` once, before their first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (listed in .gitignore).  The cache key includes
# the directory, so the path is fixed: a per-run temp directory would
# never hit.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and nothing is changed."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
