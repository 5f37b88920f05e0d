"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the directory, so a directory that moves between
runs never hits.  `enable_compile_cache` is called at the top of every
entry point (`chip_smoke.py`, `launch/serve.py`, `launch/train.py`,
`benchmarks/run.py`): where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads
it itself and nothing is set in code; otherwise the cache lives in the
checkout's `.jax_cache/` (gitignored).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(CHECKOUT_CACHE_DIR)
    if jax.config.jax_compilation_cache_dir != path:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", path)
        compilation_cache.reset_cache()   # re-read the directory
    return path
