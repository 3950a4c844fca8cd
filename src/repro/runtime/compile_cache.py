"""JAX's persistent compilation cache, placed from outside the program.

Entry points call :func:`use_compile_cache` from their ``main`` (never
at import): a later run of any of them then loads compiled programs
from disk instead of compiling them again.
"""
from __future__ import annotations

import os
import pathlib

import jax

# fixed, git-ignored directory at the checkout root: a path that moved
# between runs would never be found again
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the persistent cache directory, setting it if need be.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    it is left alone. Otherwise the cache goes to ``.jax_cache/`` at the
    checkout root."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
