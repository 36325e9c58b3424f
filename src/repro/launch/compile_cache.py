"""JAX's persistent compilation cache, placed from outside.

Every entry point (launch/txn_bench.py, the benchmarks/ scripts and
chip_smoke.py) calls ``enable_compile_cache`` once before it compiles.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this sets no
other directory; where it is not, the cache goes to ``.jax_cache`` at the
root of the checkout — a fixed path, because the path is part of the
cache's key and a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The in-checkout cache directory (listed in .gitignore).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
