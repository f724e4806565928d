"""Persistent XLA compile cache, placed from outside the library.

Entry points (`chip_smoke.py`, `bench*.py`, `examples/*.py`) call
:func:`use_compile_cache` once before their first compile; the package
itself never does (importing a library must not redirect a caller's
cache), and neither does the test suite.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["CHECKOUT_CACHE", "use_compile_cache"]

# The cache key includes the directory, so the fallback is a FIXED path in
# the checkout: a temp-, pid- or time-derived directory would never hit.
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing
    is set here. Unset: the cache goes to ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
