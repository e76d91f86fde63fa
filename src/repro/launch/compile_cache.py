"""JAX's persistent compilation cache, placed from outside or in the checkout.

A cold process on the chip spends minutes compiling the full-width serving
programs; the persistent cache turns the second run into a lookup.  The
cache key includes its directory, so the directory must not move between
runs: it is never built from a temp name, a pid or the time.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and this module
  sets no other directory.
* Otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).

Entry points call :func:`enable_compile_cache` once, before their first
compile.  Tests never call it.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def cache_dir() -> str:
    """The directory the persistent cache lives in for this process."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on at :func:`cache_dir`; returns the path."""
    import jax

    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
