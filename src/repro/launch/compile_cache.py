"""Where JAX's persistent compilation cache lives.

Entry points call :func:`enable_compile_cache` once, before their first
compile (never at import time, never from the tests).  A directory given by
``JAX_COMPILATION_CACHE_DIR`` wins, and JAX already reads that variable, so
nothing is set in code then.  Otherwise the cache sits at one fixed path in
the checkout, ``<repo>/.jax_cache``: the path is part of the cache key, so a
name that moved between runs would never hit.
"""

from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache():
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
