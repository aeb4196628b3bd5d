"""JAX's persistent compilation cache, shared by every process of a run.

A benchmark or smoke process compiles the same emulation programs as the
previous one; with the cache on, the second process loads them instead.
"""
from __future__ import annotations

import os
import pathlib

# Fixed, in-repo (and git-ignored): a cache only hits from a path that
# stays the same between runs.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to the repo's
    ``.jax_cache/``. Call before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
