"""JAX persistent compilation cache for the repo's entry points.

Called from ``chip_smoke.py``, ``repro.launch.serve_ot.main`` and
``benchmarks/run.py`` — never on library import, so tests and library users
keep JAX's own default. The cache key includes its directory, so the
directory is fixed: ``$JAX_COMPILATION_CACHE_DIR`` where set (JAX reads the
variable itself), else ``<repo root>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["use_compile_cache"]

_REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return the directory. With ``JAX_COMPILATION_CACHE_DIR`` set this sets
    nothing: JAX already uses that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE_DIR))
    return str(_REPO_CACHE_DIR)
