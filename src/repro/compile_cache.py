"""JAX's persistent compilation cache, switched on by the entry points.

Compiling a model step at published widths takes tens of seconds; the
cache lets later processes on the same machine load the program
instead.  Entry points call :func:`enable_compile_cache` first thing in
``main``; importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

# src/repro/compile_cache.py → the checkout root; a fixed path, because
# the directory is part of what makes an entry findable again
_CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep compiled programs across processes and return the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here; otherwise the cache lives in
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    return str(_CHECKOUT_CACHE)
