"""Where JAX's persistent compilation cache lives.

``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
itself and this module sets nothing.  Otherwise the cache is a fixed
directory inside the checkout (:data:`DEFAULT_DIR`, git-ignored), so
every process run from one checkout shares its compiled lane programs
and predictor steps.  The path is part of the cache's key, which is why
it never depends on a temp name, a pid or the time.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

#: <checkout>/.jax_cache (this file is <checkout>/src/repro/compile_cache.py)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get(ENV) or DEFAULT_DIR


def enable() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir`
    (idempotent); call before the process's first compile, since JAX
    decides once per process whether the cache is used.  Returns the
    directory."""
    import jax

    path = cache_dir()
    if not os.environ.get(ENV) and \
            jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
