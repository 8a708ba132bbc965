"""JAX's persistent compilation cache for the launchers and chip_smoke.py.

A cold start compiles every prefill-chunk, decode-segment, refill and
prefix program of every node; with the cache on, a later run of the same
checkout reads them back instead.  Call ``enable_compile_cache()`` once,
before the first compile.  The test suite does not call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# fixed in-checkout path (gitignored): a directory derived from a temp
# name, a pid or the clock would never be found again by the next run
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  A set
    ``JAX_COMPILATION_CACHE_DIR`` is left alone (JAX reads it itself and
    no other directory is configured); otherwise the cache is the fixed
    ``<checkout>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
