"""JAX's persistent compilation cache, in one place for every entry point.

``repro.launch.recover``, ``repro.launch.serve``, ``benchmarks/run.py`` and
``chip_smoke.py`` call :func:`enable_compile_cache` before their first
compile, so a second identical run loads its executables instead of
compiling them again.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing here
  sets another directory.
* unset: the cache lives in ``.jax_cache/`` at the root of the checkout
  (listed in ``.gitignore``). The path is fixed — never built from a
  temporary name, a pid or the time — because a directory that moves between
  runs is never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``src/repro/launch/`` → the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    configured = os.environ.get(ENV_VAR)
    if configured:
        return configured
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
