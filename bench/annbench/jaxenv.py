"""JAX settings every entry point of the benchmark shares.

The persistent compilation cache lives at ``<checkout>/.jax_cache``, a
fixed path inside the checkout (JAX keys entries on it), whatever the
environment says, so that two checkouts never share compiled programs.
Programs of every size are cached, so the second run of a cell compiles
nothing that the first compiled.
"""

from __future__ import annotations

import os
from pathlib import Path


def configure(root: Path):
    """Point JAX's cache into the checkout; returns the ``jax`` module."""
    cache = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax
