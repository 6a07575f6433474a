"""Persistent XLA compilation cache for the entry points.

`enable_compile_cache()` is called by each entry point (`chip_smoke.py`,
`launch/serve.py`, `launch/train.py`, `serving/async_server.py`,
`benchmarks/run.py`), never on import.  Where `JAX_COMPILATION_CACHE_DIR`
is set, jax reads it itself and no other directory is set in code.
Otherwise the cache lives at one fixed directory inside the checkout
(`.jax_compilation_cache`, git-ignored): the directory is part of what a
later run must find again, so it never carries a temporary name, a pid
or a time.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_compilation_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
