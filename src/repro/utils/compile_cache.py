"""JAX's persistent compilation cache at a fixed place.

The cache directory is part of what a later process must find again, so it
never moves: ``$JAX_COMPILATION_CACHE_DIR`` where that is set (JAX reads it
itself), else ``<checkout>/.jax_cache`` (git-ignored). Call
:func:`enable_compile_cache` before the first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory. A directory named by ``$JAX_COMPILATION_CACHE_DIR``
    is left to JAX's own reading of the variable."""
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return jax.config.jax_compilation_cache_dir
