"""Where the launchers keep JAX's persistent compilation cache.

A cold process compiles every program again; the persistent cache lets
later processes on the same machine reuse what an earlier one compiled.
The directory is part of what a cached entry is found by, so it must not
move between runs: it is either the one ``JAX_COMPILATION_CACHE_DIR``
names or the fixed ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn on the persistent compilation cache.  When
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already taken the
    directory from it and nothing is set here; otherwise the cache goes
    to :data:`CHECKOUT_CACHE`."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
