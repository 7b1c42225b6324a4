"""Where JAX's persistent compilation cache lives.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and wins: the
code sets no other directory. Otherwise the cache goes to ``.jax_cache/`` at
the repository root. The path is part of each entry's key, so it is fixed:
never built from a temp name, a pid or the time.

Entry points call ``enable_compile_cache()`` first thing; library code and
tests never do.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
