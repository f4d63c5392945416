"""JAX's persistent compilation cache for the entry points.

Each entry point (``launch/train.py``, ``benchmarks/run.py``,
``chip_smoke.py``) calls ``enable()`` first thing in ``main()``, before
anything compiles; nothing calls it at import.  A cold run of unreduced
llama-3.2-1b recompiles the whole round program otherwise, and that is
a large share of a short run.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets nothing.
* not set: ``<repo>/.jax_cache``, a fixed directory of the checkout
  found from this file's own path (git ignores it).  The path is part
  of a cache entry's key, so it never depends on a temporary name, a
  process id or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
