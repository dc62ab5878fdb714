"""Where JAX keeps its persistent compilation cache.

A cold serving run spends most of its first minutes compiling tick
families.  JAX finds a compiled program again only in the directory it
wrote it to, so the cache must live at a path that stays put from run
to run: never one named after a temporary directory, a pid or the time.

Call :func:`enable_compile_cache` first thing in an entry point, before
anything is compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: src/repro/compile_cache.py -> parents[2]
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Use ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it on
    its own, and nothing here overrides it); otherwise keep the cache in
    ``.jax_cache`` at the root of the checkout.  Returns the directory in
    use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
