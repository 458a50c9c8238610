"""Where JAX's persistent compilation cache lives.

Entry points that compile the simulator for a chip (``chip_smoke.py``,
``benchmarks/perf_benches.py``) call :func:`enable_persistent_cache`
once, before their first compile. Importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root: this file is ``<checkout>/src/repro/compile_cache.py``
CHECKOUT = Path(__file__).resolve().parents[2]


def enable_persistent_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.

    ``JAX_COMPILATION_CACHE_DIR``, where it is set, is the directory: JAX
    reads it itself and nothing here overrides it. Otherwise the cache
    lives at ``<checkout>/.jax_cache``, a fixed path, so that the next
    process in the same checkout finds what this one compiled.
    """
    # the simulator's spans live in op_name metadata, which JAX leaves
    # out of the cache key by default: an executable compiled before a
    # scope was added or renamed would come back with the old names
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
