"""Where JAX keeps its persistent compilation cache.

The cache directory is part of what makes an entry findable again, so it
never moves: `JAX_COMPILATION_CACHE_DIR` when the environment sets it (JAX
reads that variable itself), otherwise `.jax_cache/` at the checkout root.
Entry points call `init_compile_cache()` before their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory. Where `JAX_COMPILATION_CACHE_DIR` is set, nothing
    is set here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
