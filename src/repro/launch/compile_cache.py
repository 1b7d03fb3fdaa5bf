"""JAX's persistent compilation cache for the command-line entry points.

A cold run compiles build, anonymize, analyze, the fused program and the
sketch and stream folds, which takes minutes.  With the cache on, a later
run that finds the same directory loads those programs instead.  The
directory has to stay put between runs, so it is either the one
``JAX_COMPILATION_CACHE_DIR`` names or ``<repo>/.jax_cache``, resolved
from this file's own path.

Each entry point calls :func:`use_compile_cache` from its ``main`` when run
from the command line (``argv is None``); a caller that passes its own argv,
as the tests do, leaves JAX's configuration alone, and nothing turns the
cache on at import.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["DEFAULT_CACHE_DIR", "use_compile_cache"]

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache(config=None) -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise ``config`` (default ``jax.config``) is
    pointed at :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if config is None:
        import jax

        config = jax.config
    config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
