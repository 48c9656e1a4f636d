"""Where JAX's persistent compilation cache lives for this repo's entry
points (``chip_smoke.py``, ``examples/graph500_driver.py``,
``benchmarks/run.py``); library imports never touch it.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout: a fixed path (never a temporary name, a process id or a time),
because the path is part of what makes a later run find its entries.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Enable the persistent compilation cache; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
