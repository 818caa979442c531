"""JAX's persistent compilation cache, placed from outside the library.

Entry points (``chip_smoke.py``, ``repro.launch.serve_svm``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once, before their
first compile.  Importing ``repro`` never does: a library that moves its
users' cache behind their backs is a surprise.

The cache directory is part of each entry's key, so it must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads
it itself and nothing is set here); otherwise the cache lives at the fixed
``<checkout>/.jax_cache``, which ``.gitignore`` lists.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The checkout this module lives in: ``src/repro/launch`` -> root.
CHECKOUT = Path(__file__).resolve().parents[3]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
