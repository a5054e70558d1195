"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks.run``) calls :func:`use_compile_cache`
before it compiles anything, so repeated runs of one checkout reuse
compiled programs instead of compiling cold.

* ``JAX_COMPILATION_CACHE_DIR`` set: that directory is the cache.  JAX
  reads the variable itself, and nothing here overrides it.
* Otherwise the cache is ``.jax_cache`` at the root of the checkout (listed
  in ``.gitignore``).  The path is fixed because it is part of what the
  cache is keyed on: a directory named after a temp dir, a pid or the time
  would never be found again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
