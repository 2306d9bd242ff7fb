"""JAX's persistent compilation cache for the repo's entry points.

``enable_compile_cache()`` is called by ``chip_smoke.py`` and the
benchmark entry points, never when the library is imported.  The cache
lives where ``JAX_COMPILATION_CACHE_DIR`` says when that is set, and
otherwise at the fixed ``<checkout>/.jax_cache`` (git-ignored): the
directory is part of what a later run must find again, so it never
depends on the process, the time or a temporary name.
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Point ``jax_compilation_cache_dir`` at the cache directory and
    return it."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(CHECKOUT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
