"""JAX's persistent compilation cache, placed once for every entry point.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``
(listed in ``.gitignore``).  The path is fixed on purpose: it is part of the
cache key, so a temporary or per-process directory would never hit.
Entry points (``chip_smoke.py``, ``repro.launch.serve``, the benchmark
scripts) call :func:`enable`; library code and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
