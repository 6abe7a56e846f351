"""JAX's persistent compilation cache for the entry points.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX keeps its cache there and
nothing here overrides it. Otherwise the cache goes to `.jax_cache/` at the
repository root: a path fixed by this file's place in the checkout, never
by a temporary name, the pid, the time or the working directory, so that
each run finds what the runs before it compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX at the cache directory; returns the directory in use."""
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
