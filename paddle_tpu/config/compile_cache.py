"""Where JAX's persistent compilation cache lives.

The directory is part of the cache key's lookup path, so it must be the
same in every process that should share compiled programs: it is placed
from outside through ``JAX_COMPILATION_CACHE_DIR`` (JAX reads that
variable itself) or, when that is unset, at ``<checkout>/.jax_cache`` —
never under ``tempfile``, a pid or the time.
"""
from __future__ import annotations

import os

__all__ = ["place_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Call once, before the first compile. Returns the directory in use."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
