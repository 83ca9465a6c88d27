"""The one place that decides where JAX's persistent compilation cache lives.

`JAX_COMPILATION_CACHE_DIR`, when set, wins: JAX reads it itself and this
module sets nothing. Otherwise the cache is `<checkout>/.jax_cache` — a
fixed path (never built from a temp name, a pid or the time), so a later
run from the same checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
