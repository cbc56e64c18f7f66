"""JAX's persistent compilation cache, kept in one place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here overrides it.  Otherwise the cache lives in ``.jax_cache/``
inside the checkout, a fixed path (part of each entry's key), listed in
``.gitignore``.  Entry points call :func:`use_compile_cache` before
their first compile; the test suite does not.
"""

from __future__ import annotations

import os

import jax

__all__ = ["CACHE_ENV_VAR", "use_compile_cache"]

CACHE_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache(checkout: str | os.PathLike) -> str:
    """Turn the cache on for this process; returns its directory."""
    env = os.environ.get(CACHE_ENV_VAR)
    if env:
        return env
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
