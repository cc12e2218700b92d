"""JAX's persistent compilation cache, at one place per checkout.

``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as it is: JAX reads it
itself and nothing here overrides it. Otherwise the cache lives at a fixed
``<checkout>/.jax_cache`` (gitignored), so every process of one checkout —
the chip smoke, each benchmark — reuses what an earlier one compiled. The
path is part of the cache key, which is why it never moves.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the fixed cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
