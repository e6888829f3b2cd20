"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``) calls :func:`enable_compile_cache` before its first
compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX already reads
it, so nothing else is set.  Otherwise the cache lives at one fixed,
gitignored path inside the checkout.  The next run finds its programs
only at the same path, so it is never built from a temporary name, a pid
or the time.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
