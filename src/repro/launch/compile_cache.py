"""JAX's persistent compilation cache, placed from outside or at a fixed path.

A resumed process recompiles its train step unless the executable can be
read back from disk, so every entry point calls ``enable_compile_cache()``
before its first compile:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing else
    is set, so whoever runs the job decides where the cache lives.
  * otherwise: ``<checkout>/.jax_cache``, derived from this package's
    location.  The path is part of the cache's key, so it never holds a
    temp name, a PID or a timestamp: a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
#: src/repro/launch/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
