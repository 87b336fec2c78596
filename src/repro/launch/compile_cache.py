"""JAX's persistent compilation cache, at one fixed place per checkout.

The cache key includes the cache path, so the path must not move between
runs: it is never a temporary directory and never made from a process id
or the time.  Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.train``) call ``use_compile_cache()`` before their first
compile.
"""

from __future__ import annotations

import os
import pathlib

#: ``<repo>/.jax_cache`` — listed in ``.gitignore``.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to ``REPO_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
