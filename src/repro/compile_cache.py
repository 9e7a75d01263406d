"""Where JAX's persistent compilation cache lives.

The cache key includes the cache directory, so a directory that moves
between runs never hits. ``use_compile_cache`` is called once at the top
of every entry point that compiles real work (``chip_smoke.py`` and the
benchmark mains):

- if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  sets nothing;
- otherwise the cache goes to ``<checkout>/.jax_cache`` (gitignored), a
  path fixed by the checkout alone.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
