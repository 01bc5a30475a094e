"""JAX's persistent compile cache, placed from outside or inside the checkout.

Entry points (``chip_smoke.py``, ``repro.launch.serve_bigset``,
``benchmarks.run``) call :func:`enable_compile_cache` once, before their
first compile; importing this module changes nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and that
  directory stands — no other is set here.
* Unset: the cache goes to ``.jax_cache/`` at the root of the checkout, a
  fixed path (the path is part of the cache key) that git ignores.

Either way the minimum compile time worth caching drops to zero, so the
sub-second ``dot_seen`` kernel compiles are kept too.
"""
from __future__ import annotations

import os

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; return its directory."""
    import jax

    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = os.path.normpath(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
