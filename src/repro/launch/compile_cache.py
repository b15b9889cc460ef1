"""JAX's persistent compilation cache for the drivers and ``chip_smoke.py``.

Each process that reaches a TPU starts with no compiled code; the cache lets
the processes of one command (and, where the directory survives, later
commands) reuse executables.  One rule, so the key never moves:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself, nothing is set
  here, and it is the only cache;
* unset — the cache is ``<repo>/.jax_cache``, a fixed path inside the
  checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on per the rule above; returns its path."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
