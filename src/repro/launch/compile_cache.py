"""JAX's persistent compilation cache, at a place fixed from outside.

Entry points (``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``, ``chip_smoke.py``) call
:func:`enable_compile_cache` before their first compile, so a second run
of the same program loads its executables instead of compiling them.

* Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
  nothing is set here.
* Otherwise the cache lives at ``.jax_cache/`` in the checkout root.

The path is part of each entry's key, so it is fixed: never built from a
temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py → the checkout root is three levels up
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
