"""Persistent compilation cache for the entry points.

Scripts, benchmarks and examples call ``enable_compile_cache`` once at
start-up; importing the library never does.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and it
stands.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed
path, so a later run of the same checkout finds what an earlier one
compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax


def enable_compile_cache(checkout: Path) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
