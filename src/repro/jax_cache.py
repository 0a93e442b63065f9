"""JAX's persistent compilation cache for the entry points that can reach
the device (``python -m repro.eval``, ``benchmarks/run.py``,
``bench/run.py``, ``bench/spans.py`` and ``chip_smoke.py``'s sweep).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache goes to one fixed directory
inside the checkout, ``.jax_cache/``: the path is part of the cache key,
so it never depends on a temp name, a pid or the time.  Entry points call
:func:`enable_compile_cache` from ``main``; importing this module changes
nothing.
"""
from __future__ import annotations

import os
import pathlib

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
