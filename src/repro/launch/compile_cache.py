"""The one persistent compilation cache of this repository.

Compiling the full-size serving programs takes tens of seconds per
executable; JAX's persistent cache lets the next process load them
instead.  Its location follows one rule:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself and
    nothing is set here, so whoever runs the program places the cache;
  * unset — ``<repo>/.jax_cache`` (git-ignored).  The path is fixed, never
    derived from a temporary name, a pid or the time: it is part of what
    the cache is found by, and a moving directory never hits.

``chip_smoke.py`` and the examples call ``enable_compile_cache()`` before
their first compile; nothing else in the repository sets a cache path.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
