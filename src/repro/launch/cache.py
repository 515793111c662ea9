"""JAX's persistent compilation cache for the repo's entry-point scripts.

The cache key includes the cache's path, so the path must not move between
runs: it is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the
variable itself), and otherwise ``.jax_cache/`` at the root of the checkout
(git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout-local cache directory used when the environment names none.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
