"""JAX's persistent compilation cache at a fixed place.

The cache directory is part of what a later process must find again, so it
never moves: ``$JAX_COMPILATION_CACHE_DIR`` where that is set (JAX reads it
itself), else ``<checkout>/.jax_cache`` (git-ignored). Call
:func:`enable_compile_cache` before the first compile.

A cached executable carries the op metadata of the module that was compiled
first under its key, and JAX's key leaves metadata out by default: a hit can
hand back a step whose ``op_name`` scopes (what a trace is split by,
:mod:`repro.obs.trace`) are another version's. So the key takes the
metadata in, with source paths under the checkout written relative to it:
the key does not move with the checkout.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]
CHECKOUT_CACHE = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory. A directory named by ``$JAX_COMPILATION_CACHE_DIR``
    is left to JAX's own reading of the variable."""
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(CHECKOUT) + os.sep))
    return jax.config.jax_compilation_cache_dir
