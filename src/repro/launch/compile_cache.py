"""Where JAX's persistent compilation cache lives for the entry points.

Called from ``main()`` of the launchers (and ``chip_smoke.py``), never at
import, so library users and the test suite stay cache-free.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache: a fixed path (the directory is part of the cache's
# key, so a path built from a temp name, pid or clock would never hit).
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and left
    alone; otherwise the cache goes to :data:`DEFAULT_DIR` (git-ignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
