"""Where compiled programs persist between runs.

JAX's persistent compilation cache turns a second run's compiles into
reads. The directory is part of what makes an entry findable again, so it
must not move between runs: ``JAX_COMPILATION_CACHE_DIR``, when the
environment sets it, is honoured as JAX itself reads it; otherwise the
cache lives at one fixed directory inside the checkout (``.jax_cache/``,
ignored by git). Entry points (``chip_smoke.py``, the examples,
``benchmarks/run.py``) call :func:`enable_compile_cache` before their first
compile.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
