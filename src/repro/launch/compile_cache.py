"""JAX's persistent compilation cache, placed from outside.

A cold start of a full-width model compiles a handful of step programs
(the decode step plus one prefill step per block size); the persistent
cache lets the next process on the same machine load them instead.  The
cache key includes the directory, so the directory is fixed: it never
comes from a temporary name, a pid or the clock."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (listed in .gitignore): src/repro/launch/ -> root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to DEFAULT_DIR.  Call it before
    the first compile of the process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
