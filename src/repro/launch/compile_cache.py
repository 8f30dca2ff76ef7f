"""Where JAX keeps its persistent compilation cache.

Called by the entry points (``chip_smoke.py``, ``launch/train.py``,
``launch/serve.py``, ``benchmarks/run.py``), never at library import: a
library or a test that imports ``repro`` keeps JAX's own setting.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache is ``<checkout>/.jax_cache``:
    a fixed path, so a second run in the same checkout finds what the
    first one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
