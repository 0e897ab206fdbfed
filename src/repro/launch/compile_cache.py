"""Persistent compilation cache for the program's entry points.

Entry points (``chip_smoke.py``, ``benchmarks.run``) call
``enable_compile_cache()`` once, before their first compile; library code
and tests never do.  A cache directory is part of every entry's key, so it
must not move between runs: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and nothing here overrides it; otherwise the cache lives
at the fixed ``<repo>/.jax_cache`` (ignored by git).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
