"""Where jax keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, the benchmark
mains) call :func:`enable_compile_cache` once, before their first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it
itself and nothing is set here.  Otherwise the cache is the fixed
``.jax_cache/`` at the checkout root: a path that moved between runs
would never be hit again.  Tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
