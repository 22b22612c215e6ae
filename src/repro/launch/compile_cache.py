"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is left
alone. Otherwise the cache goes to ``<checkout>/.jax-cache``: a fixed path,
because the directory is part of what a later process must find again (a
path derived from a temp dir, a pid or the time would never hit).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["configure_compile_cache", "DEFAULT_CACHE_DIR"]

#: The checkout root is three levels above this file (src/repro/launch/).
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax-cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it.

    Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
