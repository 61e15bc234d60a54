"""Persistent XLA compilation cache for the entry points.

Call :func:`enable_compile_cache` first thing in an entry point's ``main``
(never at import).  The cache directory is part of what a later run must
find again, so it is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when that is
set, else ``<checkout>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
