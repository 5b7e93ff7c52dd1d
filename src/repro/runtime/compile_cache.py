"""JAX's persistent compilation cache, kept in one fixed place.

Entry points (``chip_smoke.py``, ``repro.launch.train``, ``benchmarks.run``)
call :func:`enable_compile_cache` before anything compiles, so a second run
of the same programs loads executables instead of compiling them again.
"""
from __future__ import annotations

import os
from pathlib import Path

# the repository checkout: src/repro/runtime/compile_cache.py -> checkout
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads the
    variable itself) and no other directory is set.  Otherwise the cache is
    ``<checkout>/.jax_cache`` — a fixed path, never derived from a temporary
    name, a pid or the time, so the next run finds what this one wrote."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
