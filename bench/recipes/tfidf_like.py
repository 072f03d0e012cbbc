"""NYTimes-like surrogate: sparse non-negative tf-idf rows, made on the device.

A copy, in ``jax.random``, of the recipe the repository's ``tfidf_like``
generator follows: 24 topics ~ Dirichlet(0.05), one topic per document,
document length exp(N(3, 1)), Poisson term counts, 15% of terms kept,
then idf weighting over all rows. Document length spans two orders of
magnitude, so cosine and euclidean rank differently. Rows are made in
blocks; the idf pass holds the output twice at most.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BLOCK = 4096


@functools.partial(jax.jit,
                   static_argnames=("n_rows", "d", "n_topics", "density"))
def generate(key, *, n_rows: int, d: int, n_topics: int = 24,
             density: float = 0.15):
    """f32[n_rows, d] from ``key``."""
    k_t, k_rows = jax.random.split(key)
    topics = jax.random.dirichlet(k_t, jnp.full((d,), 0.05, jnp.float32),
                                  (n_topics,))
    n_blocks = -(-n_rows // BLOCK)

    def block(i):
        k_i = jax.random.fold_in(k_rows, i)
        k_doc, k_len, k_cnt, k_mask = jax.random.split(k_i, 4)
        doc_topic = jax.random.randint(k_doc, (BLOCK,), 0, n_topics)
        length = jnp.exp(3.0 + jax.random.normal(k_len, (BLOCK, 1)))
        lam = topics[doc_topic] * length * d
        counts = jax.random.poisson(k_cnt, lam, (BLOCK, d)).astype(jnp.float32)
        keep = jax.random.uniform(k_mask, (BLOCK, d)) < density
        return counts * keep

    x = jax.lax.map(block, jnp.arange(n_blocks))
    x = x.reshape(n_blocks * BLOCK, d)[:n_rows]
    df = jnp.sum(x > 0, axis=0).astype(jnp.float32)
    idf = jnp.log((n_rows + 1.0) / (1.0 + df))
    return x * idf
