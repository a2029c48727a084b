"""Persistent XLA compilation cache for the entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` on its own: when it is set, this
module sets nothing. Otherwise the cache is the fixed ``.jax_cache/`` at
the root of the checkout. The directory is part of what lets a later run
find an entry, so it never depends on a temporary path, a PID or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
