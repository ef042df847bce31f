"""Persistent XLA compile cache at a fixed path.

A process that compiles the serving step at published widths spends
minutes doing so.  JAX's persistent cache keeps each compiled program on
disk so the next process with the same programs loads it instead.  The
directory must stay the same from one process to the next, so it is
never built from a temporary name, a pid or a time.

JAX's key for a program leaves out op metadata, where the mixed step's
named scopes live (``runner.STEP_SCOPES``).  A program compiled from
code with other scopes, or none, would then load in place of this
code's, and a device trace would name its ops by the old scopes.  So the
scope vocabulary goes into every key, through JAX's hook for additions
to it.
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
    key_scopes()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def key_scopes() -> str:
    """Add the mixed step's scope vocabulary to every cache key; returns
    what is added."""
    from jax._src import cache_key

    from repro.serving.runner import STEP_SCOPES
    tag = "step-scopes:" + ",".join(STEP_SCOPES)
    cache_key.custom_hook = lambda: tag
    return tag
