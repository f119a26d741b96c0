"""JAX's persistent compilation cache for the entry points.

Call :func:`enable_compile_cache` before the first compile.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it at import and this
sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path, because the directory is part of every entry's key, so a
temporary or per-run directory would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE", "enable_compile_cache"]

#: the cache directory used when JAX_COMPILATION_CACHE_DIR is unset
#: (this file is <checkout>/src/repro/launch/compile_cache.py)
CHECKOUT_CACHE = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
