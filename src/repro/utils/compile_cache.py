"""Persistent JAX compilation cache at a place the caller can predict.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call ``use_compile_cache`` once before their first
compile; library code and tests never do.  The cache directory is part of
what a later run must find again, so it is a fixed path: the
``JAX_COMPILATION_CACHE_DIR`` that the environment names (JAX reads the
variable itself), or ``.jax_cache/`` at the checkout root.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
