"""Placement of jax's persistent compilation cache.

The directory is part of the cache key's world: a path that moves (a
temp name, a pid, a timestamp) never hits.  So the place is decided
from outside where possible and is otherwise one fixed path:

* ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and this
  module sets nothing in code;
* otherwise ``<checkout>/.jax_cache`` (git-ignored).

Called once, before the first compile, by the programs that compile
for the chip (``chip_smoke.py``, ``bench.py``, ``tools/lm_bench.py``).
The library's own entry points set no cache: the caller's process
decides.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on (see module docstring)
    and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_entries(path: str) -> int:
    """How many compiled programs ``path`` holds (0 before it exists)."""
    if not os.path.isdir(path):
        return 0
    return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
