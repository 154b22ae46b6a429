"""JAX's persistent compilation cache, turned on by the entry points.

Call :func:`enable_compile_cache` from a ``main`` (never at import time).
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets no other directory.  Otherwise the cache lives at the fixed
``<repo>/.jax_cache`` (gitignored): the directory is part of the cache
key, so a path that moved between runs would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compile_cache", "REPO_CACHE_DIR"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
