"""JAX's persistent compilation cache, placed from outside the program.

A compiled program is keyed on, among other things, the directory it is
cached in, so the directory must not move between runs: a path built
from a temp name, a pid or the time never hits.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <repo>/.jax_cache: src/repro/compile_cache.py -> src/repro -> src -> repo
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns
    its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
