"""Where JAX's persistent compilation cache lives.

Call :func:`enable` before the first compile. If ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing else is set here. Otherwise the cache
goes to the fixed path ``<checkout>/.jax_cache``: the directory is part of
the cache key, so a path that moves between runs (a temp dir, a pid, a time)
would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
