"""Persistent XLA compilation cache, placed from outside.

Every entry point that compiles on the chip (``chip_smoke.py``,
``bench.py``) calls :func:`enable_compile_cache` before its first compile.
The directory is part of the cache key's world: a path that moves between
runs (tempfile, pid, time, cwd) never hits, so there are exactly two
places it can be:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; nothing is
  configured in code, so whoever runs the program owns the placement;
* otherwise ``<checkout>/.jax_cache`` (git-ignored), found from this
  file's own location.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_entries(path: str) -> int:
    """Entries the cache directory holds now (0 when it does not exist) —
    printed by the entry points so a compile time is never read without
    knowing whether the cache was warm."""
    try:
        return len(os.listdir(path))
    except FileNotFoundError:
        return 0
