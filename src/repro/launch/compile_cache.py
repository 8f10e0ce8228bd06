"""JAX persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples)
call :func:`enable_compile_cache` once at start-up; library import never
does, so tests keep JAX's defaults.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it as
  ``jax_compilation_cache_dir``; nothing is overridden.
* unset: the cache goes to ``<checkout>/.jax_cache`` (git-ignored). The
  path is fixed because it is part of the cache key: a directory that
  moves between runs never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
