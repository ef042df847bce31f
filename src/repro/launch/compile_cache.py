"""Persistent XLA compile cache at a fixed path.

A process that compiles the serving step at published widths spends
minutes doing so.  JAX's persistent cache keeps each compiled program on
disk so the next process with the same programs loads it instead.  The
directory must stay the same from one process to the next, so it is
never built from a temporary name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (listed in .gitignore)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is overridden.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
