"""GLOVE-like surrogate: an anisotropic Gaussian mixture, made on the device.

A copy, in ``jax.random``, of the recipe the repository's ``dense_embed``
generator follows (64 components, centres ~ N(0, 2^2), per-component
per-axis scales ~ U(0.3, 1.2)), so the yardstick does not move with the
program's data module. Rows are made in blocks, so the device holds the
output and one block of temporaries.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BLOCK = 4096


@functools.partial(jax.jit, static_argnames=("n_rows", "d", "n_comp"))
def generate(key, *, n_rows: int, d: int, n_comp: int = 64):
    """f32[n_rows, d] from ``key``."""
    k_c, k_s, k_rows = jax.random.split(key, 3)
    centers = jax.random.normal(k_c, (n_comp, d), jnp.float32) * 2.0
    scales = jax.random.uniform(k_s, (n_comp, d), jnp.float32, 0.3, 1.2)
    n_blocks = -(-n_rows // BLOCK)

    def block(i):
        k_i = jax.random.fold_in(k_rows, i)
        k_comp, k_z = jax.random.split(k_i)
        comp = jax.random.randint(k_comp, (BLOCK,), 0, n_comp)
        z = jax.random.normal(k_z, (BLOCK, d), jnp.float32)
        return centers[comp] + z * scales[comp]

    x = jax.lax.map(block, jnp.arange(n_blocks))
    return x.reshape(n_blocks * BLOCK, d)[:n_rows]
