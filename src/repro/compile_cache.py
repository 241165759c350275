"""JAX persistent compilation cache for the repo's entry points.

The sweep dispatcher compiles one kernel per padded (B, E, L, W) shape,
so a cold process spends much of its start compiling. Entry points (the
launcher, ``chip_smoke.py``, ``examples/``, ``benchmarks/``) call
:func:`enable_compile_cache` once at start; importing ``repro`` never
touches the cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no directory. Otherwise the cache lives at a fixed path
inside the checkout (``<repo>/.jax_cache``, listed in ``.gitignore``),
found from this module's own path: it never depends on a temp name, a
pid or a time, so a later process finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Every
    compile is cached, however short: the many small per-shape kernel
    compiles are exactly what a cold run pays for."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
