"""Persistent XLA compilation cache for the entry points.

Scripts that a user runs (``chip_smoke.py``, ``benchmarks/run.py``,
``examples/*.py``) call :func:`enable_compile_cache` once, before they
compile anything, so a second run loads its compiled programs instead of
compiling them again.  Importing ``repro`` never turns the cache on.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["enable_compile_cache", "CACHE_DIR"]

# A fixed path inside the checkout, so every run from one checkout
# reads what an earlier run wrote.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and nothing is changed; otherwise the cache goes to
    ``<checkout>/.jax_cache`` and keeps every program, however quickly
    it compiled (JAX's default skips those under a second)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(CACHE_DIR)
