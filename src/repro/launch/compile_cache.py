"""Where JAX keeps its persistent compilation cache.

A full-width train step takes over a minute to compile, so every entry
point that drives the device calls :func:`enable_compile_cache` before
its first compile. The directory comes from ``JAX_COMPILATION_CACHE_DIR``
when that is set; otherwise it is ``<checkout>/.jax_cache`` — a fixed
path, because the path is part of what a later run must match to hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root (src/repro/launch/compile_cache.py -> 3 levels up)
ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_
    CACHE_DIR`` or, when unset, at :data:`DEFAULT_DIR`. Returns the
    directory in use."""
    path = os.environ.get(ENV) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
