"""JAX's persistent compilation cache for the entry points.

Every entry point (`repro.launch.train`, `repro.launch.serve_gnn`, the
benchmarks, `chip_smoke.py`) calls `enable_compile_cache()` before its
first compile, so processes of one checkout share compiled executables.
The cache directory is part of each entry's key: it is one fixed path,
never built from a temporary name, a process id or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# `.jax_cache/` at the root of the checkout (listed in .gitignore)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set.  Otherwise the cache goes to `DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
