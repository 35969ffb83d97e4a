"""Where JAX keeps its persistent compilation cache.

Entry points call `enable_compile_cache()` once, before their first
compile.  A process started with `JAX_COMPILATION_CACHE_DIR` set already
uses that directory (JAX reads the variable itself), so nothing else is
set.  Otherwise the cache goes to the fixed `<repo>/.jax_cache`: the path
is part of the cache key, so it never depends on a temp name, pid or time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
