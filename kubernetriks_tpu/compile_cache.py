"""Placement of JAX's persistent compilation cache for the entry points
(cli.py, chip_smoke.py).

The chip tool starts every call with no compiled code, and the path is
part of the cache's key, so the directory must be fixed and placeable from
outside: JAX_COMPILATION_CACHE_DIR wins (JAX reads it itself; nothing is
set in code), otherwise the cache lives at <checkout>/.jax_cache
(git-ignored). The recompile sentinel still sees a retrace served from
this cache: JAX logs "Finished XLA compilation of" around
compile_or_get_cached, hit or miss (tests/test_compile_cache.py)."""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def place_compile_cache() -> str:
    """Returns the cache directory in effect. Call before the first jit
    dispatch; importing this module touches neither JAX nor the disk."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
