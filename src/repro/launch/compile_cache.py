"""JAX's persistent compile cache for the programs that run on a chip.

Entry points (``launch/*`` and ``chip_smoke.py``) call
:func:`enable_compile_cache` once at start; nothing calls it at import
time or from tests. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and no other path is set here. Otherwise the cache
lives at the fixed ``<repo>/.jax_cache``: a cache entry is only found
again under the same directory, so the path never depends on a
temporary directory, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
