"""Where the persistent XLA compilation cache lives.

Entry points (chip_smoke.py, examples/) call ``use_compile_cache()``
before their first compile; library code and the tests never do. The
cache key includes its directory, so the directory is fixed: either the
one ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself,
and nothing else is set), or ``.jax_cache`` at the root of this
checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
