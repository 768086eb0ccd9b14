"""JAX's persistent compilation cache, placed from outside the program.

A cold serving process compiles every prefill bucket, every prefix-step
bucket, both serve-step variants and the pow2 delta-scatter shapes.  The
persistent cache keeps those executables between processes.  Entry points
(``launch/serve.py``, ``chip_smoke.py``, the engine benchmarks' ``main``)
call ``enable_compile_cache()`` once before they compile anything; nothing
calls it on import, so tests run without a cache.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# fixed, inside the checkout (and listed in .gitignore): a path that
# changed between runs would start empty every time and never hit
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no other directory.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``.
    """
    import jax

    where = os.environ.get(ENV_VAR)
    if where:
        return where
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
