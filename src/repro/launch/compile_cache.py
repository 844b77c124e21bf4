"""Where the persistent XLA compile cache lives — one rule for every entry
point (``chip_smoke.py``, the serve and train CLIs).

``JAX_COMPILATION_CACHE_DIR``, when set, wins: jax reads it itself and no
other directory is set in code.  Otherwise the cache goes to ``.jax_cache/``
at the root of the checkout (gitignored).  The path is part of each cache
key, so it is fixed: never a temporary name, a process id or a timestamp.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
