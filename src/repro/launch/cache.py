"""Placement of JAX's persistent compilation cache, shared by every entry
point (training and serving CLIs, benchmarks, ``chip_smoke.py``)."""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

_CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Use ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
    itself, so nothing is set in code); otherwise point the cache at
    ``<checkout>/.jax_cache``.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
