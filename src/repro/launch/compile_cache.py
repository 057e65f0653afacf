"""Where JAX's persistent compilation cache lives for the launchers.

``JAX_COMPILATION_CACHE_DIR``, when set, places the cache from outside: JAX
reads that variable itself, so nothing is set here.  Otherwise the cache goes
to a fixed ``.jax_cache/`` at the root of the checkout.  The directory is part
of what a later run must find again, so it never moves between runs."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
