"""Persistent XLA compile cache for the chip entry points.

A chip run starts from an empty process, and compiling the solver and
farm programs is a large share of a cold run.  JAX's persistent cache
keeps compiled executables on disk; the directory is part of what makes
an entry findable again, so it lives at one fixed place.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache lives at the fixed
    ``.jax_cache/`` of this checkout (ignored by git).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
