"""JAX's persistent compilation cache, at one fixed place per checkout.

Every entry point calls :func:`enable_compile_cache` before it compiles
anything.  A cache entry is keyed by, among other things, the directory
it lives in, so a path with a temp, pid or time component would never
hit again; the default is therefore the fixed ``<checkout>/.jax_cache``
(listed in ``.gitignore``).  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already reads it at import, and this module sets no other directory.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the repository root: src/repro/launch/compile_cache.py -> parents[3]
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The directory the cache lives in: the environment's, else the
    checkout's fixed ``.jax_cache``."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return that directory.  Call it before the first compile: JAX
    settles the cache on first use."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
