"""Persistent compilation cache for the command-line entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; where it is set, nothing
here overrides it. Otherwise the cache goes to ``.jax_cache`` at the root
of the checkout, a fixed path (the path is part of every entry's key, so a
directory that moves never hits). Entry points call
:func:`enable_compile_cache` first thing; library code and tests never do.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
