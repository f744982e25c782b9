"""Where JAX's persistent compilation cache lives for this repo's entry points.

A compiled TPU program is keyed on, among other things, the cache
directory's path, so a directory that moves between runs never hits. The
entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.serve_spgemm``) call :func:`enable_compile_cache` once at
start-up; importing a module never turns the cache on.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

# <checkout>/.jax_cache (listed in .gitignore): fixed, so it hits across runs
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads the
    variable itself and nothing is set here. Otherwise the cache is
    :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
