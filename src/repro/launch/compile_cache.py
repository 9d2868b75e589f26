"""JAX's persistent compile cache, set at program entry.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and that
directory is the cache.  Otherwise the cache goes to ``.jax_cache`` at
the root of the checkout: a fixed path, since the path is part of every
cache key.  Programs call `enable` first thing in ``main``; importing the
library never touches the cache.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the cache on for this process; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        import jax
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
