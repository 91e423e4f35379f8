"""JAX's persistent compilation cache for the repo's entry points.

Scripts (``chip_smoke.py``, ``examples/*.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` first thing; importing the library never
does. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
the helper sets nothing. Otherwise the cache lives at the fixed
``<repo>/.jax_cache`` (listed in ``.gitignore``): the path is part of the
cache key, so a directory that moved between runs would never hit.

Examples
--------
>>> import os
>>> from repro.launch.cache import REPO_CACHE_DIR
>>> os.path.basename(REPO_CACHE_DIR)
'.jax_cache'
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: ``src/repro/launch/`` → the checkout root.
REPO_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache",
))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
