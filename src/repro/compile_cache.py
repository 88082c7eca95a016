"""JAX's persistent compilation cache, at one fixed place.

Entry points call :func:`use_compile_cache` once, before they compile.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
this leaves it alone; otherwise the cache lives in ``.jax_cache/`` at the
root of the checkout.  The directory is fixed, never derived from a
temporary directory, a pid or the time, because the path is part of
what a later run must find again.

An entry's key includes the program's op metadata.  That metadata holds
the named scopes a profiler trace reports for each op; keyed without it,
an edit that only moves a scope would load the executable compiled
before the edit, and its trace would show the old scopes.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
