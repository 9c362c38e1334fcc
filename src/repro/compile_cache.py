"""JAX persistent compilation cache for the repo's entry points.

``enable()`` is called first thing by ``chip_smoke.py``,
``repro.launch.serve`` and ``benchmarks.run`` (never by the tests):

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; this sets
  nothing, so compiled programs land there and nowhere else.
* unset: the cache goes to ``<checkout>/.jax_cache`` (git-ignored).
  The path is fixed — never a temp name, pid or time — because it is
  part of the cache key: a directory that moves never hits.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
