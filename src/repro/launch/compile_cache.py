"""Where the entry points keep JAX's persistent compilation cache.

Called by the entry points only (``chip_smoke.py``, ``python -m
repro.launch.serve`` and ``python -m benchmarks.run``) -- never on import,
never by library code or tests, so importing :mod:`repro` changes no JAX
configuration.

The path is part of the cache's key, so it is fixed: an exported
``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads that variable itself, and
nothing is set here), otherwise ``.jax_cache/`` at the root of the
checkout, derived from this file's location.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Point the persistent compile cache at its fixed home; returns it."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
