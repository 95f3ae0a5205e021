"""JAX's persistent compilation cache for the repo's entry points.

Compiling the paper U-Net's serving programs takes about a minute each, so
every entry point (``chip_smoke.py`` and the launchers) turns the cache on
through :func:`use_compile_cache` before its first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache — a fixed path (it is part of the cache key, so a
# moving directory would never hit), listed in .gitignore
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent cache and return its directory.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
    else is set; otherwise the cache goes to :data:`REPO_CACHE`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
