"""JAX persistent compilation cache location for the entry points.

A cold process compiles every executable it runs; at the paper's shapes
that is minutes of set-up. JAX keys its persistent cache on the directory
too, so the directory must not move between runs.

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing is set
here. Unset: the cache goes to ``<checkout>/.jax_cache`` (git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def enable() -> str:
    """Turn the persistent cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
