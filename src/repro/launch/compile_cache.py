"""Where JAX's persistent compilation cache lives.

Call :func:`enable` before the first compile. If ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and no directory is set here. Otherwise the cache
goes to the fixed path ``<checkout>/.jax_cache``: the directory is part of
the cache key, so a path that moves between runs (a temp dir, a pid, a time)
would never hit.

Op metadata (the ``engine.LAYERS`` scopes, source lines) is part of the key:
JAX leaves it out by default, and an executable compiled by a program that
differs from this one in metadata alone would then be loaded with that
program's op names, so a profile would attribute its ops wrongly. Source
paths have the checkout's root cut off (as imported and as resolved), so a
checkout that moves still hits. The price: an edit that moves a traced
line compiles afresh.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = ROOT / ".jax_cache"
# the checkout's root, as a source path may spell it
_ROOTS = {str(ROOT), os.path.abspath(Path(__file__).parents[3])}
CHECKOUT_PREFIX = "^(?:" + "|".join(
    re.escape(r) for r in sorted(_ROOTS, key=len, reverse=True)) + ")"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      CHECKOUT_PREFIX)
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
