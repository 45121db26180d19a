"""JAX's persistent compilation cache for the entry points.

Entry points (`chip_smoke.py`, `repro.launch.serve`, the bench mains)
call `enable_compile_cache` first; importing this module does nothing.
Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this
sets no other path.  Otherwise the cache lives at ``<repo>/.jax_cache``:
a fixed path, because the path is part of each entry's key, so a
directory named from a temp name, a pid or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
