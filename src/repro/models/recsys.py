"""Recsys architectures: Wide&Deep, xDeepFM, DIN, AutoInt.

The common skeleton is: huge sparse embedding tables -> feature-interaction
op -> small MLP -> CTR logit. JAX has no native EmbeddingBag or CSR sparse,
so the lookup layer is built here from ``jnp.take`` + ``jax.ops.segment_sum``
(:func:`embedding_bag` fixed-length masked form for the static-shape hot
path, :func:`embedding_bag_ragged` true-ragged form for the input pipeline).

Distribution: the tables are the only large state — all ``n_sparse`` field
tables are stacked into one flat ``[F * rows, D]`` array, row-sharded over
the ``model`` axis (the recsys analogue of TP); lookups become partitioned
gathers. Interaction/MLP weights are tiny and replicated; the batch is
sharded over the data axes.

``retrieval_step`` implements the ``retrieval_cand`` shape: one user vector
scored against 10^6 candidate embeddings — a batched-dot top-k, sharded over
the candidate rows with the same butterfly merge PDASC's distributed search
uses (this is the paper-representative cell; the PDASC-index-accelerated
variant is benchmarked in ``benchmarks/bench_retrieval.py``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Array = jax.Array

KINDS = ("wide_deep", "xdeepfm", "din", "autoint")


@dataclasses.dataclass(frozen=True)
class RecsysConfig:
    name: str
    kind: str
    n_sparse: int
    embed_dim: int
    n_dense: int = 13  # numeric features (criteo-style); 0 to disable
    table_rows: int = 1_000_000  # rows per sparse field
    mlp: tuple = ()
    cin_layers: tuple = ()  # xdeepfm
    seq_len: int = 0  # din behaviour-sequence length
    attn_mlp: tuple = ()  # din attention MLP
    n_attn_layers: int = 0  # autoint
    n_attn_heads: int = 0
    d_attn: int = 0
    retrieval_dim: int = 64
    dtype: Any = jnp.float32

    @property
    def flat_rows(self) -> int:
        return self.n_sparse * self.table_rows

    def n_params(self) -> int:
        shapes = jax.tree.leaves(param_shapes(self))
        return sum(int(math.prod(s.shape)) for s in shapes)


# ---------------------------------------------------------------------------
# EmbeddingBag (take + segment_sum — JAX has neither natively)
# ---------------------------------------------------------------------------


def embedding_bag(
    table: Array, ids: Array, mask: Optional[Array] = None,
    combiner: str = "mean",
) -> Array:
    """Fixed-length bag: ids [..., L] -> [..., D]; masked sum/mean."""
    e = jnp.take(table, ids, axis=0)  # [..., L, D]
    if mask is not None:
        e = e * mask[..., None].astype(e.dtype)
    s = jnp.sum(e, axis=-2)
    if combiner == "mean":
        n = (jnp.sum(mask, axis=-1, keepdims=True).astype(e.dtype)
             if mask is not None else e.shape[-2])
        s = s / jnp.maximum(n, 1.0)
    return s


def embedding_bag_ragged(
    table: Array, flat_ids: Array, segment_ids: Array, n_segments: int,
    combiner: str = "mean",
) -> Array:
    """True-ragged bag: CSR-style (values, segment) -> [n_segments, D]."""
    e = jnp.take(table, flat_ids, axis=0)
    s = jax.ops.segment_sum(e, segment_ids, num_segments=n_segments)
    if combiner == "mean":
        cnt = jax.ops.segment_sum(
            jnp.ones_like(flat_ids, e.dtype), segment_ids, num_segments=n_segments
        )
        s = s / jnp.maximum(cnt[:, None], 1.0)
    return s


def field_lookup(tables_flat: Array, ids: Array, rows_per_field: int) -> Array:
    """Per-field embedding: ids [B, F] into stacked tables [F*R, D] -> [B, F, D]."""
    F = ids.shape[-1]
    offsets = jnp.arange(F, dtype=ids.dtype) * rows_per_field
    return jnp.take(tables_flat, ids + offsets, axis=0)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def _mlp_shapes(dims: Sequence[int], prefix: str, pd) -> dict:
    out = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        out[f"{prefix}_w{i}"] = jax.ShapeDtypeStruct((a, b), pd)
        out[f"{prefix}_b{i}"] = jax.ShapeDtypeStruct((b,), pd)
    return out


def _interaction_in_dim(cfg: RecsysConfig) -> int:
    F, D = cfg.n_sparse, cfg.embed_dim
    if cfg.kind == "wide_deep":
        return cfg.n_dense + F * D
    if cfg.kind == "xdeepfm":
        return cfg.n_dense + F * D
    if cfg.kind == "din":
        return 3 * D + cfg.n_dense
    if cfg.kind == "autoint":
        return F * cfg.n_attn_heads * cfg.d_attn
    raise ValueError(cfg.kind)


def param_shapes(cfg: RecsysConfig) -> dict:
    pd = jnp.float32
    F, R, D = cfg.n_sparse, cfg.table_rows, cfg.embed_dim
    p: dict = dict(tables=jax.ShapeDtypeStruct((F * R, D), pd))
    mlp_in = _interaction_in_dim(cfg)
    mlp_dims = (mlp_in,) + tuple(cfg.mlp) + (1,)
    p.update(_mlp_shapes(mlp_dims, "mlp", pd))

    if cfg.kind == "wide_deep":
        p["wide"] = jax.ShapeDtypeStruct((F * R, 1), pd)
        if cfg.n_dense:
            p["wide_dense"] = jax.ShapeDtypeStruct((cfg.n_dense, 1), pd)
    elif cfg.kind == "xdeepfm":
        hs = (F,) + tuple(cfg.cin_layers)
        for i, (h_prev, h) in enumerate(zip(hs[:-1], hs[1:])):
            p[f"cin_w{i}"] = jax.ShapeDtypeStruct((h, h_prev, F), pd)
        p["cin_out"] = jax.ShapeDtypeStruct((sum(cfg.cin_layers), 1), pd)
        p["lin"] = jax.ShapeDtypeStruct((F * R, 1), pd)
    elif cfg.kind == "din":
        # attention MLP on [e_t, e_b, e_t - e_b, e_t * e_b]
        p.update(_mlp_shapes((4 * D,) + tuple(cfg.attn_mlp) + (1,), "attn", pd))
    elif cfg.kind == "autoint":
        H, da, L = cfg.n_attn_heads, cfg.d_attn, cfg.n_attn_layers
        d_in = D
        for l in range(L):
            for nm in ("wq", "wk", "wv"):
                p[f"attn{l}_{nm}"] = jax.ShapeDtypeStruct((d_in, H * da), pd)
            p[f"attn{l}_wres"] = jax.ShapeDtypeStruct((d_in, H * da), pd)
            d_in = H * da
    # retrieval user-tower projection (shared across kinds)
    penult = (cfg.mlp[-1] if cfg.mlp else mlp_in)
    p["retrieval_proj"] = jax.ShapeDtypeStruct((penult, cfg.retrieval_dim), pd)
    return p


def param_specs(cfg: RecsysConfig, batch_axes=("data",), model_axis="model"):
    """Tables (and wide/lin vectors) row-sharded over ``model``; rest replicated."""
    shapes = param_shapes(cfg)
    specs = {}
    for k, s in shapes.items():
        if k in ("tables", "wide", "lin"):
            specs[k] = P(model_axis, None)
        else:
            specs[k] = P(*([None] * len(s.shape)))
    return specs


def init_params(cfg: RecsysConfig, key: Array) -> dict:
    shapes = param_shapes(cfg)
    out = {}
    for name, s in shapes.items():
        key, sub = jax.random.split(key)
        if name.endswith(tuple(f"_b{i}" for i in range(8))):
            out[name] = jnp.zeros(s.shape, s.dtype)
        else:
            fan_in = s.shape[0] if len(s.shape) > 1 else s.shape[0]
            scale = 0.01 if name in ("tables", "wide", "lin") else 1.0 / math.sqrt(fan_in)
            out[name] = (jax.random.normal(sub, s.shape, jnp.float32) * scale).astype(s.dtype)
    return out


def _mlp_apply(p, prefix, x, n_layers, act=jax.nn.relu, return_penult=False):
    penult = x
    for i in range(n_layers):
        x = x @ p[f"{prefix}_w{i}"] + p[f"{prefix}_b{i}"]
        if i < n_layers - 1:
            x = act(x)
            penult = x
    return (x, penult) if return_penult else x


def _n_mlp_layers(cfg: RecsysConfig) -> int:
    return len(cfg.mlp) + 1


# ---------------------------------------------------------------------------
# Forward passes (logit [B])
# ---------------------------------------------------------------------------


def _forward_wide_deep(params, batch, cfg):
    emb = field_lookup(params["tables"], batch["sparse"], cfg.table_rows)
    B, F, D = emb.shape
    parts = [emb.reshape(B, F * D)]
    if cfg.n_dense:
        parts.append(batch["dense"])
    deep_in = jnp.concatenate(parts, axis=-1)
    logit_deep, penult = _mlp_apply(params, "mlp", deep_in, _n_mlp_layers(cfg),
                                    return_penult=True)
    wide = embedding_bag(params["wide"], batch["sparse"], combiner="sum")  # [B,1]
    logit = logit_deep[:, 0] + wide[:, 0]
    if cfg.n_dense:
        logit = logit + (batch["dense"] @ params["wide_dense"])[:, 0]
    return logit, penult


def _forward_xdeepfm(params, batch, cfg):
    emb = field_lookup(params["tables"], batch["sparse"], cfg.table_rows)
    B, F, D = emb.shape
    # CIN: x_k[b, h, d] = sum_{i, j} W_k[h, i, j] * x_{k-1}[b, i, d] * x_0[b, j, d]
    x0, xk = emb, emb
    pooled = []
    for i in range(len(cfg.cin_layers)):
        z = jnp.einsum("bhd,bfd->bhfd", xk, x0)
        xk = jnp.einsum("bhfd,ohf->bod", z, params[f"cin_w{i}"])
        pooled.append(jnp.sum(xk, axis=-1))  # [B, h]
    logit_cin = (jnp.concatenate(pooled, axis=-1) @ params["cin_out"])[:, 0]
    parts = [emb.reshape(B, F * D)]
    if cfg.n_dense:
        parts.append(batch["dense"])
    dnn_in = jnp.concatenate(parts, axis=-1)
    logit_dnn, penult = _mlp_apply(params, "mlp", dnn_in, _n_mlp_layers(cfg),
                                   return_penult=True)
    lin = embedding_bag(params["lin"], batch["sparse"], combiner="sum")[:, 0]
    return logit_cin + logit_dnn[:, 0] + lin, penult


def _din_interest(params, e_seq, e_t, seq_mask, cfg):
    """Target attention over the behaviour sequence -> interest vector."""
    L = e_seq.shape[1]
    et_b = jnp.broadcast_to(e_t[:, None, :], e_seq.shape)
    a_in = jnp.concatenate([et_b, e_seq, et_b - e_seq, et_b * e_seq], axis=-1)
    scores = _mlp_apply(params, "attn", a_in, len(cfg.attn_mlp) + 1)[..., 0]
    scores = jnp.where(seq_mask > 0, scores, -1e30)
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(e_seq.dtype)
    return jnp.einsum("bl,bld->bd", w, e_seq)


def _forward_din(params, batch, cfg):
    D = cfg.embed_dim
    # Field 0 of the stacked tables is the item table (targets + behaviours).
    e_t = jnp.take(params["tables"], batch["target"], axis=0)  # [B, D]
    e_seq = jnp.take(params["tables"], batch["seq"], axis=0)  # [B, L, D]
    interest = _din_interest(params, e_seq, e_t, batch["seq_mask"], cfg)
    parts = [interest, e_t, interest * e_t]
    if cfg.n_dense:
        parts.append(batch["dense"])
    x = jnp.concatenate(parts, axis=-1)
    logit, penult = _mlp_apply(params, "mlp", x, _n_mlp_layers(cfg),
                               return_penult=True)
    return logit[:, 0], penult


def _forward_autoint(params, batch, cfg):
    emb = field_lookup(params["tables"], batch["sparse"], cfg.table_rows)
    B, F, _ = emb.shape
    H, da = cfg.n_attn_heads, cfg.d_attn
    x = emb
    for l in range(cfg.n_attn_layers):
        q = (x @ params[f"attn{l}_wq"]).reshape(B, F, H, da)
        k = (x @ params[f"attn{l}_wk"]).reshape(B, F, H, da)
        v = (x @ params[f"attn{l}_wv"]).reshape(B, F, H, da)
        s = jnp.einsum("bfhd,bghd->bhfg", q, k) / math.sqrt(da)
        w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
        o = jnp.einsum("bhfg,bghd->bfhd", w, v).reshape(B, F, H * da)
        x = jax.nn.relu(o + x @ params[f"attn{l}_wres"])
    flat = x.reshape(B, F * H * da)
    logit, penult = _mlp_apply(params, "mlp", flat, _n_mlp_layers(cfg),
                               return_penult=True)
    return logit[:, 0], penult


_FORWARDS = dict(
    wide_deep=_forward_wide_deep,
    xdeepfm=_forward_xdeepfm,
    din=_forward_din,
    autoint=_forward_autoint,
)


def forward(params, batch, cfg: RecsysConfig):
    """Returns (ctr logits [B], penultimate representation [B, h])."""
    return _FORWARDS[cfg.kind](params, batch, cfg)


def loss_fn(params, batch, cfg: RecsysConfig, sh=None, mesh=None):
    logits, _ = forward(params, batch, cfg)
    y = batch["labels"].astype(jnp.float32)
    z = logits.astype(jnp.float32)
    # numerically-stable BCE-with-logits
    loss = jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))
    return loss, {"logit_mean": jnp.mean(z)}


# ---------------------------------------------------------------------------
# Retrieval (the `retrieval_cand` shape)
# ---------------------------------------------------------------------------


def user_vector(params, batch, cfg: RecsysConfig) -> Array:
    """[B, retrieval_dim] user-tower output."""
    _, penult = forward(params, batch, cfg)
    return penult @ params["retrieval_proj"]


def retrieval_step(params, batch, candidates, cfg: RecsysConfig, mesh=None,
                   *, k: int = 100, cand_axes=("data", "model")):
    """Score one user against [n_cand, retrieval_dim] candidates, top-k.

    With a mesh, candidates are row-sharded and the per-shard top-k are
    butterfly-merged (same collective as distributed PDASC search).
    """
    u = user_vector(params, batch, cfg)  # [B, Dr]
    if mesh is None:
        scores = u @ candidates.T  # [B, n_cand]
        top, idx = jax.lax.top_k(scores, k)
        return top, idx.astype(jnp.int32)

    from repro.core.distributed import topk_merge

    n = candidates.shape[0]
    Pn = 1
    for a in cand_axes:
        Pn *= mesh.shape[a]
    per = n // Pn

    def body(u_rep, cand_local):
        shard = jnp.int32(0)
        for a in cand_axes:
            shard = shard * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        scores = u_rep @ cand_local[0].T  # [B, per]
        top, idx = jax.lax.top_k(scores, k)
        gids = idx.astype(jnp.int32) + shard * jnp.int32(per)
        return topk_merge(-top, gids, tuple(cand_axes), k)  # ascending -score

    fn = jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(P(), P(tuple(cand_axes), None, None)),
        out_specs=(P(), P()),
    )
    negs, ids = fn(u, candidates.reshape(Pn, per, candidates.shape[-1]))
    return -negs, ids
