"""Persistent XLA compilation cache for the entry points.

A cold serve of a full-width model compiles one decode program and one
prefill program per page-count bucket; the cache lets the next process on
the same checkout load them instead.  Only entry points call
``enable_compile_cache`` — importing the library never touches JAX config.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: a fixed path, because the directory is part of
# what a later run has to find again
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no other; otherwise the cache goes to ``DEFAULT_DIR``."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
