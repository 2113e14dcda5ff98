"""Persistent compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
helper sets nothing.  Otherwise the cache lives at a fixed
``<repo>/.jax_cache``: the directory is part of the cache key, so a path
that moves between runs would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
