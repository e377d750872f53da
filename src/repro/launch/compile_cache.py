"""JAX's persistent compilation cache, set up once by every entry point.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing
else is set here.  Otherwise the cache lives at a fixed `.jax_cache/` in the
repository root: the path is part of the cache key, so a directory that
moved between runs would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
