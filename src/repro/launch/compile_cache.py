"""JAX persistent compilation cache at a fixed place.

Entry points (``chip_smoke.py``, ``examples/train_lm.py``,
``examples/serve_lm.py``) call :func:`enable_compile_cache` before they
compile anything. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and this module sets no other directory. Otherwise the
cache lives in ``<repo>/.jax_cache``: a fixed path, because the path is
part of what a later process must find again (never a temp, pid or
time-based directory).
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

# src/repro/launch/compile_cache.py -> the repository root.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
