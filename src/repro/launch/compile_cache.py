"""JAX's persistent compilation cache for the entry points.

Called at the start of each launcher, benchmark and ``chip_smoke.py`` —
never when ``repro`` is imported, so tests and library users keep JAX's
own default (no cache).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, never a temp name: the cache only hits when the path is stable
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads
    the variable itself) and no other is set; otherwise the cache goes to
    ``.jax_cache/`` at the repo root."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
