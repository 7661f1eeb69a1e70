"""Process-level JAX settings every entry point makes before the backend
comes up: which platform, and where compiled programs are kept.

Imports no JAX at module level, so an entry point can call in here first.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

#: Where compiled programs are kept when nobody says otherwise: a fixed,
#: git-ignored directory inside the checkout. Fixed because the directory
#: is part of a cache entry's key — one that moves never hits.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def force_cpu() -> None:
    """The `--cpu` flag of the entry points: hold JAX to the CPU.

    `JAX_PLATFORMS=cpu` is all it takes, and child processes inherit it.
    JAX reads the variable once, when it is imported, and every entry
    point of this package has imported it (with its own package) by the
    time it parses arguments — so an already imported JAX is told too.
    Must run before the first device is touched."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_platforms", "cpu")


def place_compile_cache() -> str:
    """Give JAX's persistent compilation cache a home, and return it.

    Where `JAX_COMPILATION_CACHE_DIR` is set the cache is placed from
    outside: JAX reads the variable itself and nothing is touched here.
    Otherwise the cache goes to `COMPILE_CACHE_DIR`. Every cold start of a
    32-layer model recompiles minutes of programs without one."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
