"""Where JAX keeps compiled programs between processes.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``,
``repro.launch``) call :func:`enable` once, before their first compile;
importing a module never does.  The cache path is part of what JAX finds
an entry by, so it is fixed: ``JAX_COMPILATION_CACHE_DIR`` when that is
set (JAX reads it itself and nothing here overrides it), else
``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# a hashing kernel compiles in ~1-2 s; JAX's default threshold (1 s)
# would drop the faster ones
MIN_COMPILE_TIME_S = 0.1


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_TIME_S)
    return path
