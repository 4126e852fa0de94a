"""Where JAX's persistent compilation cache lives.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the
cache lives there; this module then sets nothing. Otherwise the cache lives
in ``<repo>/.jax_cache`` (listed in ``.gitignore``). The path is fixed on
purpose: the directory is part of what a later run must find again, so it
is never built from a temporary name, a process id or the time.

Call :func:`enable_compile_cache` before the first compilation.
"""

from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
