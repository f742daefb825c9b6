"""Persistent XLA compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``repro.launch.solve_serve``,
``benchmarks/run.py``, ``examples/*``) call :func:`enable_compile_cache`
once at start-up; nothing calls it at import.  JAX keys cache entries by
directory, so the location is fixed: ``JAX_COMPILATION_CACHE_DIR`` when
the environment sets it (JAX reads that variable itself, and no other
directory is set), else ``<checkout>/.jax_cache`` (listed in
``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
