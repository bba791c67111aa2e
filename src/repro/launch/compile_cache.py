"""JAX's persistent compilation cache, placed once for every entry point.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives at one fixed path inside
the checkout (``.jax_cache/``, git-ignored): the directory is part of
the cache key, so a temp-, pid- or time-derived name would never hit.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory.

    Call before the first compilation.  Sets ``jax_compilation_cache_dir``
    only when the environment does not already name a directory.
    """
    import jax

    path = os.environ.get(_ENV)
    if not path:
        path = _DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
