"""JAX persistent compilation cache at a fixed path.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks.run``)
call :func:`setup_compile_cache` first thing in ``main()``; importing this
module changes nothing.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and this sets nothing.  Otherwise the cache goes to
``<checkout>/.jax_cache``, resolved from this file's own location, so every
process run from one checkout shares one cache whatever its working
directory (the cache key includes the path, so a moving directory never
hits).
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
