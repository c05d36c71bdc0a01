"""Where JAX keeps its persistent compilation cache for long runs.

Entry points that compile for minutes (``chip_smoke.py``,
``bench/run.py``) call :func:`use_compile_cache` first thing; the
library itself never does, so importing :mod:`repro` leaves JAX's cache
settings alone.
"""
from __future__ import annotations

import os

# fixed, never a temporary, per-process or per-run path: the directory is
# part of the cache's key, so a later run finds only what was kept here
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache in the checkout's
    ``.jax_cache``, unless ``JAX_COMPILATION_CACHE_DIR`` is set: JAX then
    reads that directory itself and no other is set here.  Returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
