"""JAX's persistent compilation cache for the entry points that compile.

``chip_smoke.py``, ``python -m benchmarks.run`` and ``python -m
repro.service.load`` call :func:`enable_compile_cache` before their first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing is set here.  Otherwise the cache lives at one fixed directory
inside the checkout (``.jax_cache``, gitignored): the directory is part of
what a later run must find again, so it never carries a temporary name, a
process id or a time.

The cache key includes each program's op metadata (its ``jax.named_scope``
names and source lines), which JAX strips from the key by default: a
program that differs from a cached one only in its scopes would otherwise
load that executable, and the HLO a profiler stores with a trace would not
carry the running code's scopes.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
