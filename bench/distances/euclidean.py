"""Plain euclidean distance for the reference: sqrt(sum((x - q)^2))."""

import jax.numpy as jnp


def from_gram(g, qq, xx):
    """Distances from the Gram matrix ``g = q @ x.T`` and squared norms
    (the reference's top-k pass): [m, n]."""
    return jnp.sqrt(jnp.maximum(qq[:, None] + xx[None, :] - 2.0 * g, 0.0))


def direct(q, c):
    """Exact distances of candidate rows by subtraction, no matmul:
    q [m, d], c [m, j, d] -> [m, j]."""
    diff = c - q[:, None, :]
    return jnp.sqrt(jnp.sum(diff * diff, axis=-1))
