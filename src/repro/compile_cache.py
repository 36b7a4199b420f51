"""Where JAX keeps its persistent compilation cache for this repo's programs.

A process that compiles for the chip calls ``place_compile_cache`` before its
first compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it
itself and nothing here overrides it.  Otherwise the cache goes to
``<repo>/.jax_cache`` — a fixed path, so a later process in the same checkout
finds what an earlier one compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def place_compile_cache() -> str:
    """Turn the persistent cache on for every compile, however short (the
    reader kernels compile in about a second), and return its directory."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
