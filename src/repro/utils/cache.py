"""Where JAX keeps its persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is unset, the cache
lives at a fixed directory inside the checkout, so every process of a run
(and a later run in the same checkout) finds what an earlier one compiled.
The directory must not move between runs: it is part of the cache's key.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``CACHE_DIR`` unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; returns the directory in use.
    Call before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
