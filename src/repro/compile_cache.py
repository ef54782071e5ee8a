"""JAX's persistent compilation cache, placed from outside the program.

Entry points call :func:`enable` before their first compile::

    from repro import compile_cache
    cache_dir = compile_cache.enable()

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads the variable
itself and nothing is set here.  Otherwise the cache lives at a fixed
directory inside the checkout (``.jax_cache``, listed in ``.gitignore``),
so every later process of the same checkout finds what an earlier one
compiled; a temporary or per-process path would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the checkout-local default: <repo>/.jax_cache
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
