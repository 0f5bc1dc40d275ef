"""JAX persistent compilation cache at one fixed place.

``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX (it reads the
variable itself) and no other directory is set in code.  Otherwise the
cache goes to ``<repo>/.jax_cache`` — a fixed path, since the directory
is part of what a later process must find again to hit the cache.
"""

from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
