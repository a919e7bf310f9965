"""JAX's persistent compilation cache for this repo's entry points.

``enable_compile_cache()`` is called at the start of every entry
point's ``main()`` (never at import).  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and this sets no other directory.
Otherwise the cache lives at the checkout's fixed ``.jax_cache/``
(listed in ``.gitignore``): the path is part of what a later run looks
up, so it never depends on a temporary name, a pid or the time.
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
