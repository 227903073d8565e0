"""Where JAX keeps compiled programs between processes.

A cold start of the trainer compiles its meta step, which takes about a
minute at published widths.  JAX's persistent compilation cache keeps the
compiled programs on disk; the cache directory is part of each entry's key,
so it has to be a fixed path.
"""
from __future__ import annotations

import os
import pathlib

import jax

# src/repro/launch/compile_cache.py -> the checkout root
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else at ``.jax_cache/``
    in the checkout.  Returns the directory."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(_CHECKOUT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
