"""Plain cosine distance for the reference: 1 - x.q / (|x| |q|).

A zero row has no direction; its norm is held at 1e-6, so its distance to
every row is 1.
"""

import jax.numpy as jnp

_NORM_FLOOR = 1e-6


def _norm(sq):
    return jnp.maximum(jnp.sqrt(sq), _NORM_FLOOR)


def from_gram(g, qq, xx):
    """Distances from the Gram matrix ``g = q @ x.T`` and squared norms
    (the reference's top-k pass): [m, n]."""
    cos = g / (_norm(qq)[:, None] * _norm(xx)[None, :])
    return 1.0 - jnp.clip(cos, -1.0, 1.0)


def direct(q, c):
    """Exact distances of candidate rows, no matmul:
    q [m, d], c [m, j, d] -> [m, j]."""
    dot = jnp.sum(c * q[:, None, :], axis=-1)
    qn = _norm(jnp.sum(q * q, axis=-1))
    cn = _norm(jnp.sum(c * c, axis=-1))
    return 1.0 - jnp.clip(dot / (qn[:, None] * cn), -1.0, 1.0)
