"""Where JAX keeps its persistent compilation cache.

A directory set in ``JAX_COMPILATION_CACHE_DIR`` is left to JAX, which
reads the variable itself.  Otherwise the cache goes to ``.jax_cache`` at
the root of the checkout: a fixed path, because the path is part of the
cache's key and a directory that moves never hits.  Entry points call
``use_compile_cache`` before their first compile; importing this module
changes nothing.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """-> the directory the process's compilation cache writes to."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
