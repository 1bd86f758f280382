"""JAX's persistent compilation cache, for the repo's entry-point scripts.

`enable()` is called by `chip_smoke.py`, `benchmarks/run.py` and
`examples/quickstart.py` before their first compile.  Importing the library
never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache: a fixed path, because the directory is part of
#: what a later process must find again
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, jax already reads it and
    nothing is set here.  Otherwise programs are cached in the checkout's
    `.jax_cache/` — never a path holding a temp name, a pid or a time."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
