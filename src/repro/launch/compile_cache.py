"""JAX's persistent compilation cache, one rule for every entry point.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
module sets nothing.  Otherwise the cache lives at ``<repo>/.jax_cache``: a
fixed path, because the path is part of the cache key, and inside the
checkout, because the program writes nothing outside it.  Libraries never
call this; entry points (`chip_smoke.py`, `repro.launch.train`,
`repro.launch.serve`) call it once at start-up.
"""
from __future__ import annotations

import os
import pathlib

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
