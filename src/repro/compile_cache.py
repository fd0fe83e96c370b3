"""Where JAX's persistent compilation cache lives, for every entry point.

``chip_smoke.py``, ``benchmarks/common.py`` and ``python -m
repro.serve.bench`` call ``enable_compile_cache()`` once, before their
first compile.  Importing this module imports nothing from jax, so
``import repro`` still initialises no backend.
"""
from __future__ import annotations

import os
from pathlib import Path

# a FIXED directory in the checkout: the cache only hits when every process
# of every run looks in the same place, so the path must never be built
# from a temporary name, a pid or a time
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads the
    directory from the environment and no directory is set in code.
    Otherwise the cache goes to ``<checkout>/.jax_cache`` (gitignored).
    jax's 1 s floor on what it caches drops to 0.1 s, so the per-shard-shape
    steps (well under a second each) are cached too.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return path
