"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (``chip_smoke.py``, ``repro.launch.train``,
``benchmarks.run``) call ``configure_compile_cache`` once at start-up.
A ``JAX_COMPILATION_CACHE_DIR`` set in the environment wins and JAX
reads it itself; otherwise the cache lives at ``<checkout>/.jax_cache``.
The path is fixed (no temp name, pid or time), because it is part of
the cache's key: a directory that moves between runs never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
