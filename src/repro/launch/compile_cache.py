"""Where JAX keeps its persistent compilation cache.

A compiled program is found again only when the cache directory stays the
same from run to run, so the directory is fixed: the one named by
``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it itself), and
``<repo>/.jax_cache`` otherwise.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn on the persistent compilation cache and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
