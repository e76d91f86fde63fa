"""JAX's persistent compilation cache, placed from outside or in the checkout.

A cold process on the chip spends minutes compiling the full-width serving
programs; the persistent cache turns the second run into a lookup.  The
cache key includes its directory, so the directory must not move between
runs: it is never built from a temp name, a pid or the time.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and this module
  sets no other directory.
* Otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).

The key also covers the programs' metadata (op names and their
``jax.named_scope`` paths, source lines): an entry compiled from code
whose scopes have since changed would otherwise be found again, and a
profile of it would show the old scopes.  Source paths enter the
metadata relative to the checkout, so the key does not depend on where
the checkout lies.

Entry points call :func:`enable_compile_cache` once, before their first
compile.  Tests never call it.
"""

from __future__ import annotations

import os
import re

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
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(CHECKOUT + os.sep))
    return path
