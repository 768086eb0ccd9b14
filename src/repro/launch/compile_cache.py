"""JAX's persistent compilation cache, placed from outside the program.

A cold serving process compiles every prefill bucket, every prefix-step
bucket, both serve-step variants and the pow2 delta-scatter shapes.  The
persistent cache keeps those executables between processes.  Entry points
(``launch/serve.py``, ``chip_smoke.py``, the engine benchmarks' ``main``)
call ``enable_compile_cache()`` once before they compile anything; nothing
calls it on import, so tests run without a cache.

The cache is keyed on each program's metadata too (``key_on_metadata``).
JAX's key strips it by default, and a named scope is only metadata: a
cache shared with a build from before the scope existed hands back that
build's executable, and a profile then shows code that did not run.
"""
from __future__ import annotations

import os
import re

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
# fixed, inside the checkout (and listed in .gitignore): a path that
# changed between runs would start empty every time and never hit
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def key_on_metadata() -> None:
    """Key the persistent cache on each program's metadata (named scopes,
    source locations) as well as its instructions.  An edited line of a
    program then misses the cache once; a profile always names the code
    that ran.  A location is the op's own line alone, not the call path
    that first traced its program (a delta upload first reached from
    another engine phase would miss), and its file is named from the
    checkout's root, so that a checkout moved elsewhere still hits."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(CHECKOUT + os.sep))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on, keyed on metadata;
    returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no other directory.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``.
    """
    import jax

    key_on_metadata()
    where = os.environ.get(ENV_VAR)
    if where:
        return where
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
