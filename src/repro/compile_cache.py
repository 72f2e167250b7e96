"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/*.py``)
call :func:`enable_compile_cache` before anything compiles; importing
``repro`` never does. A set ``JAX_COMPILATION_CACHE_DIR`` wins and is left
alone (JAX reads it itself); otherwise the cache goes to ``.jax_cache`` at
the repository root, a fixed path, so later runs of the same checkout hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
