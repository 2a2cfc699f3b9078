"""JAX's persistent compilation cache for the launchers.

A cold start compiles every step program, which at full width costs
minutes; the persistent cache lets the next process read them back.  The
cache key includes the directory, so the directory must not move between
runs: it is ``JAX_COMPILATION_CACHE_DIR`` when that is set, otherwise the
fixed ``.jax_cache/`` at the repository root (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return it.  Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
