"""JAX's persistent compilation cache, placed from outside or in the checkout.

A later run finds the cache only where an earlier one left it, so the
path is fixed, never derived from a temp name, pid or time.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it into its
config and nothing is changed here; otherwise the cache lives at
``<checkout>/.jax_cache`` (listed in ``.gitignore``).  Entry points call :func:`use_compile_cache` before
their first compile; importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout cache directory used when the environment names none
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory:
    the environment's where ``JAX_COMPILATION_CACHE_DIR`` is set,
    :data:`CHECKOUT_CACHE_DIR` otherwise."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
