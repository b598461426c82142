"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so a directory that moves never hits.
:func:`setup_compile_cache` is called once at start-up by the entry points
that compile at real size (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.train``):

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and the helper
  sets nothing.
* otherwise: the fixed ``<checkout>/.jax_cache`` (listed in ``.gitignore``),
  so a second run from the same checkout finds what the first compiled.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def setup_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
