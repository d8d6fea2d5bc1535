"""JAX's persistent compilation cache, kept at one fixed place.

A full-width serve tick or prefill takes tens of seconds to compile; the
persistent cache lets a second process of the same checkout load it
instead.  The cache key includes the directory, so the directory must not
move between runs: it is either the one ``JAX_COMPILATION_CACHE_DIR``
names or ``.jax_cache`` at the root of this checkout (listed in
``.gitignore``).
"""
from __future__ import annotations

import os

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: the in-checkout default (``src/repro/`` -> checkout root)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; call before the first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
