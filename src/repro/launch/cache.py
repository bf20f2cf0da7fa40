"""Where JAX keeps its persistent compilation cache.

A call on the chip starts with no compiled code, and a whole train step
takes a minute to compile. The cache keys entries partly by its path, so it
lives at one fixed place: ``JAX_COMPILATION_CACHE_DIR`` when that is set
(JAX reads it itself), else ``.jax_cache/`` at the repository root.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
