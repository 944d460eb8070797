"""Persistent JAX compilation cache for the entry points.

Every entry point's ``main`` calls :func:`enable_compile_cache` before
its first compile, so a second run of the same program (the next LTFB
job, a restarted server) loads its executables from disk instead of
compiling them again.  Nothing here runs at import time.
"""
from __future__ import annotations

import os
from pathlib import Path

# the root of the checkout (src/repro/launch/ -> three levels up): a
# fixed path, so each run finds what the previous one wrote
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, decides: JAX reads it
    itself and nothing is changed here.  Otherwise the cache lives in
    ``.jax_cache/`` at the root of the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
