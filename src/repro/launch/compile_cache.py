"""Where JAX keeps its persistent compilation cache.

Entry points that run on the chip (``chip_smoke.py``, ``benchmarks/run.py``)
call :func:`enable_compile_cache` once before their first compile.  A
``JAX_COMPILATION_CACHE_DIR`` set in the environment wins and nothing is
set in code (JAX reads the variable itself).  Otherwise the cache lives at
the fixed ``<repo>/.jax_cache``: the path is part of the cache key, so it
must not move between runs.  Tests never turn the cache on.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
