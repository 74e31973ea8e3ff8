"""Where JAX keeps its persistent compilation cache.

A compiled program is keyed by, among other things, the cache directory's
path, so a directory that moves between runs never hits. The launchers and
``chip_smoke.py`` therefore place it the same way every time.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it at import and
    nothing else is set here. Otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache``."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
