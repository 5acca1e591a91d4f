"""Where JAX's persistent compilation cache lives for a run of this repo.

A cold TPU run spends much of its time compiling; the persistent cache lets
a later run of the same programs skip that.  The cache's directory is part
of what a later run has to find again, so it is a fixed path, never one
built from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os

import jax

__all__ = ["use_compile_cache"]

ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache(checkout: str) -> str:
    """Turn on the persistent compilation cache for a program started from
    ``checkout`` and return its directory.  Where ``JAX_COMPILATION_CACHE_DIR``
    is set, JAX reads it itself and nothing is set here; otherwise the cache
    is ``<checkout>/.jax_cache`` (listed in ``.gitignore``).  Call at a
    program's entry point, never at import time."""
    path = os.environ.get(ENV)
    if path:
        return path
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
