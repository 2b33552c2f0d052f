"""JAX's persistent compilation cache, set up in one place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
other directory is set here.  Otherwise the cache lives at
``<repo>/.jax_cache`` (git-ignored): a fixed path, because the path is part
of what a later run must match to hit the cache.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return d
