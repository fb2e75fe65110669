"""JAX's persistent compilation cache for the command-line entry points.

Called from ``main`` of ``chip_smoke.py`` and of the benchmark CLIs,
never at import: a library import must not change process-wide JAX
configuration.
"""
from __future__ import annotations

import os

import jax

__all__ = ["checkout_cache_dir", "enable_compile_cache"]


def checkout_cache_dir(entry: str) -> str:
    """``.jax_cache`` at the root of the checkout holding ``entry`` (an
    entry script's path): the nearest directory above it that holds
    ``pyproject.toml``.  The directory is fixed and listed in
    ``.gitignore``; a temp-, pid- or time-based path would never hit.
    Raises when ``entry`` is not inside a checkout."""
    d = os.path.dirname(os.path.abspath(entry))
    while not os.path.isfile(os.path.join(d, "pyproject.toml")):
        parent = os.path.dirname(d)
        if parent == d:
            raise RuntimeError(f"{entry} is not inside a checkout (no "
                               "pyproject.toml above it); set "
                               "JAX_COMPILATION_CACHE_DIR")
        d = parent
    return os.path.join(d, ".jax_cache")


def enable_compile_cache(entry: str) -> str:
    """Give the persistent compilation cache a directory; return it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here; otherwise the cache goes to
    :func:`checkout_cache_dir` of the entry script ``entry`` (pass the
    script's ``__file__``).
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = checkout_cache_dir(entry)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
