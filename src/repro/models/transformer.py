"""Decoder-only transformer LMs (dense + MoE) for the assigned architectures.

Pure-functional JAX (no flax): params are nested dicts of arrays, layers are
stacked ``[L, ...]`` and driven by ``lax.scan``. Distribution is GSPMD-first —
every parameter carries a ``PartitionSpec`` (2D: tensor-parallel over the
``model`` axis x FSDP over the batch axes), activations get
``with_sharding_constraint`` at layer boundaries, and XLA inserts the
collectives. The MoE block is the exception: expert parallelism uses an
explicit ``shard_map`` (sort-based dispatch + ``all_to_all``), because its
communication pattern (a2a over the expert axis) is one GSPMD does not find
on its own.

Features mapped to the assignment's archs:
  * GQA        — ``n_kv_heads < n_heads`` (minitron/granite/qwen3), MHA when
                 equal (stablelm, deepseek-moe).
  * MoE        — top-k routing, shared experts (deepseek: 2 shared + 64
                 routed top-6; qwen3: 128 routed top-8), load-balance aux
                 loss, capacity-bounded sort dispatch, EP over ``model``.
  * Training   — causal LM, flash-style chunked attention (online softmax,
                 O(S) activation memory), chunked vocab cross-entropy (never
                 materialises ``[B, S, V]``), per-layer remat.
  * Decode     — ``serve_step``: single-token step against a sequence-sharded
                 KV cache (decode_32k shards S over ``model``; long_500k over
                 every axis). Distributed softmax/LSE-merge falls out of
                 GSPMD reductions over the sharded S dim.

Dtype policy: params are stored in ``param_dtype`` (fp32 master), cast to
``dtype`` (bf16) for compute; all softmax/norm/loss math accumulates in fp32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Array = jax.Array


# ---------------------------------------------------------------------------
# Configs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0  # shared (always-on) experts, deepseek-style
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None  # defaults to d_model // n_heads
    moe: Optional[MoEConfig] = None
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    seq_chunk: int = 2048  # chunked-xent sequence chunk
    kv_chunk: int = 1024  # flash-attention KV block
    remat: bool = True
    # Roofline-probe knobs: XLA's cost analysis counts while-loop bodies
    # once, so the dry-run probes lower 1-2 layers UNROLLED to measure exact
    # per-layer flops/bytes (launch.dryrun extrapolates to n_layers).
    scan_layers: bool = True  # False: python loop over layers
    unroll_inner: bool = False  # True: fully unroll flash/xent scans

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def vocab_padded(self) -> int:
        """Vocab rounded up to a TP-shardable multiple (Megatron-style);
        padded logit columns are masked to -inf in the loss."""
        return -(-self.vocab // 256) * 256

    def n_params(self) -> int:
        """Total parameter count (embeddings included)."""
        d, hd, H, KV, V = self.d_model, self.hd, self.n_heads, self.n_kv_heads, self.vocab
        attn = d * H * hd + 2 * d * KV * hd + H * hd * d
        if self.moe:
            m = self.moe
            ffn = m.n_experts * 3 * d * m.d_ff_expert + d * m.n_experts
            ffn += m.n_shared * 3 * d * m.d_ff_expert
        else:
            ffn = 3 * d * self.d_ff
        per_layer = attn + ffn + 2 * d
        return self.n_layers * per_layer + 2 * V * d + d

    def n_active_params(self) -> int:
        """Params touched per token (MoE: routed top-k + shared only)."""
        if not self.moe:
            return self.n_params()
        m = self.moe
        d = self.d_model
        routed_all = m.n_experts * 3 * d * m.d_ff_expert
        routed_active = m.top_k * 3 * d * m.d_ff_expert
        return self.n_params() - self.n_layers * (routed_all - routed_active)


@dataclasses.dataclass(frozen=True)
class ShardingConfig:
    """Logical-axis assignment onto the physical mesh."""

    batch_axes: tuple = ("data",)  # DP for activations, FSDP for params
    model_axis: str = "model"  # TP for heads/ffn/vocab, EP for experts
    # KV-cache sequence sharding for decode (per shape; configs decide).
    cache_seq_axes: tuple = ("model",)
    cache_batch_axes: tuple = ()

    @property
    def b(self):
        if not self.batch_axes:
            return None
        return self.batch_axes if len(self.batch_axes) != 1 else self.batch_axes[0]

    @property
    def m(self):
        return self.model_axis


def _cast(t, dtype):
    return jax.tree.map(lambda a: a.astype(dtype) if a.dtype in (jnp.float32, jnp.bfloat16, jnp.float16) else a, t)


def _shard(x, spec):
    """with_sharding_constraint under an active mesh; no-op otherwise."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x


# ---------------------------------------------------------------------------
# Parameter structure + shardings
# ---------------------------------------------------------------------------


def param_shapes(cfg: TransformerConfig) -> dict:
    """ShapeDtypeStructs of every parameter (dry-run friendly: no allocation)."""
    d, hd, H, KV, V, L = (
        cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads, cfg.vocab_padded,
        cfg.n_layers,
    )
    pd = cfg.param_dtype
    f = lambda *s: jax.ShapeDtypeStruct(s, pd)
    layer = dict(
        ln1=f(L, d),
        ln2=f(L, d),
        wq=f(L, d, H * hd),
        wk=f(L, d, KV * hd),
        wv=f(L, d, KV * hd),
        wo=f(L, H * hd, d),
    )
    if cfg.moe:
        m = cfg.moe
        layer.update(
            router=f(L, d, m.n_experts),
            we_gate=f(L, m.n_experts, d, m.d_ff_expert),
            we_up=f(L, m.n_experts, d, m.d_ff_expert),
            we_down=f(L, m.n_experts, m.d_ff_expert, d),
        )
        if m.n_shared:
            ffs = m.n_shared * m.d_ff_expert
            layer.update(
                ws_gate=f(L, d, ffs), ws_up=f(L, d, ffs), ws_down=f(L, ffs, d)
            )
    else:
        layer.update(
            w_gate=f(L, d, cfg.d_ff),
            w_up=f(L, d, cfg.d_ff),
            w_down=f(L, cfg.d_ff, d),
        )
    return dict(
        embed=f(V, d),
        layers=layer,
        final_norm=f(d),
        lm_head=f(d, V),
    )


def param_specs(cfg: TransformerConfig, sh: ShardingConfig) -> dict:
    """PartitionSpec per parameter: TP over ``model``, FSDP over batch axes.

    Layer params carry a leading L (scan) dim, never sharded.
    """
    b, m = sh.b, sh.m
    layer = dict(
        ln1=P(None, None),
        ln2=P(None, None),
        wq=P(None, b, m),
        wk=P(None, b, m),
        wv=P(None, b, m),
        wo=P(None, m, b),
    )
    if cfg.moe:
        layer.update(
            router=P(None, b, None),
            we_gate=P(None, m, b, None),
            we_up=P(None, m, b, None),
            we_down=P(None, m, None, b),
        )
        if cfg.moe.n_shared:
            layer.update(
                ws_gate=P(None, b, m), ws_up=P(None, b, m), ws_down=P(None, m, b)
            )
    else:
        layer.update(
            w_gate=P(None, b, m), w_up=P(None, b, m), w_down=P(None, m, b)
        )
    return dict(
        embed=P(m, b),
        layers=layer,
        final_norm=P(None),
        lm_head=P(b, m),
    )


def init_params(cfg: TransformerConfig, key: Array) -> dict:
    """Random init (smoke tests / examples; the dry-run never calls this)."""
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree.flatten(shapes)
    keys = jax.random.split(key, len(flat))

    def one(k, s):
        fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
        scale = 1.0 / math.sqrt(max(fan_in, 1))
        init = jax.random.normal(k, s.shape, jnp.float32) * scale
        return init.astype(s.dtype)

    params = jax.tree.unflatten(treedef, [one(k, s) for k, s in zip(keys, flat)])
    # Norm scales start at 1.
    params["final_norm"] = jnp.ones_like(params["final_norm"])
    params["layers"]["ln1"] = jnp.ones_like(params["layers"]["ln1"])
    params["layers"]["ln2"] = jnp.ones_like(params["layers"]["ln2"])
    return params


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def rmsnorm(x: Array, scale: Array, eps: float) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def rope(x: Array, positions: Array, theta: float) -> Array:
    """Rotary embedding. x: [..., S, n_heads, hd], positions: [..., S]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (math.log(theta) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(ang)[..., None, :]  # [..., S, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def swiglu(x: Array, w_gate: Array, w_up: Array, w_down: Array) -> Array:
    g = jax.nn.silu(x @ w_gate)
    return (g * (x @ w_up)) @ w_down


def flash_attention(
    q: Array, k: Array, v: Array, *, causal: bool, kv_chunk: int,
    q_offset: int = 0, unroll: bool = False,
) -> Array:
    """Online-softmax attention, O(S_kv / chunk) memory.

    q: [B, Sq, H, hd]; k, v: [B, Skv, H, hd] (kv heads already repeated).
    Scans over KV chunks keeping running (max, sum, acc) — the flash trick in
    pure JAX (the Pallas analogue lives on real TPUs; see DESIGN.md §3.3).
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    c = min(kv_chunk, Skv)
    if Skv % c:
        c = Skv  # fallback: single chunk
    n_chunk = Skv // c
    scale = 1.0 / math.sqrt(hd)

    qf = q.astype(jnp.float32) * scale
    kc = k.astype(jnp.float32).reshape(B, n_chunk, c, H, hd).transpose(1, 0, 2, 3, 4)
    vc = v.astype(jnp.float32).reshape(B, n_chunk, c, H, hd).transpose(1, 0, 2, 3, 4)

    q_pos = q_offset + jnp.arange(Sq)

    def step(carry, xs):
        m, l, acc = carry
        kb, vb, j = xs
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb)  # [B, H, Sq, c]
        if causal:
            kv_pos = j * c + jnp.arange(c)
            mask = q_pos[:, None] >= kv_pos[None, :]
            s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # Guard fully-masked rows (m_new = -inf): exp(-inf - -inf) -> nan.
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vb)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    acc0 = jnp.zeros((B, H, Sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0), (kc, vc, jnp.arange(n_chunk)),
        unroll=n_chunk if unroll else 1,
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, Sq, H, hd]


def _repeat_kv(k: Array, n_rep: int) -> Array:
    """[B, S, KV, hd] -> [B, S, KV * n_rep, hd]."""
    if n_rep == 1:
        return k
    B, S, KV, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (B, S, KV, n_rep, hd)).reshape(
        B, S, KV * n_rep, hd
    )


# ---------------------------------------------------------------------------
# MoE block (expert parallel via shard_map)
# ---------------------------------------------------------------------------


def _moe_local(x_flat, router_w, we_gate, we_up, we_down, *, moe: MoEConfig,
               model_axis: str, ep: int, dtype):
    """Per-device MoE: route -> sort-dispatch -> a2a -> expert ffn -> a2a -> combine.

    x_flat: [T, d] local tokens. we_*: [E_loc, ...] local expert shards
    (E_loc = E / ep). Runs inside shard_map; ``ep`` = model-axis size.
    """
    E, k = moe.n_experts, moe.top_k
    T, d = x_flat.shape

    logits = (x_flat.astype(jnp.float32)) @ router_w.astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)  # [T, k]
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

    # Load-balance aux loss (Switch-style): E * sum_e f_e * p_e.
    me = jnp.mean(probs, axis=0)  # [E]
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=1), axis=0
    ) / k
    aux = E * jnp.sum(me * ce)

    # ---- sort-based capacity dispatch (no [T, E, C] one-hot) ---------------
    C = max(1, int(math.ceil(moe.capacity_factor * T * k / E)))
    flat_e = top_e.reshape(-1)  # [T*k]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]  # ascending expert ids
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(E))  # [E]
    pos = jnp.arange(T * k) - seg_start[sorted_e]  # position within expert
    keep = pos < C
    token_of = order // k  # source token per sorted slot
    dst = jnp.where(keep, sorted_e * C + pos, E * C)  # overflow -> dump slot

    xe = jnp.zeros((E * C + 1, d), dtype).at[dst].set(
        x_flat[token_of].astype(dtype), mode="drop"
    )[: E * C].reshape(E, C, d)

    # ---- expert parallelism: exchange expert shards over the model axis ----
    if ep > 1:
        xe = jax.lax.all_to_all(xe, model_axis, split_axis=0, concat_axis=1,
                                tiled=True)  # [E/ep, C*ep, d]
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, we_gate.astype(dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, we_up.astype(dtype))
    ye = jnp.einsum("ecf,efd->ecd", h, we_down.astype(dtype))  # [E/ep, C*ep, d]
    if ep > 1:
        ye = jax.lax.all_to_all(ye, model_axis, split_axis=1, concat_axis=0,
                                tiled=True)  # [E, C, d]

    # ---- combine: gather each token's k slots, weight, sum ------------------
    ye_flat = jnp.concatenate([ye.reshape(E * C, d), jnp.zeros((1, d), dtype)])
    slot_of = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.where(keep, dst, E * C).astype(jnp.int32)
    )  # undo the sort: slot per (token, k)
    y_slots = ye_flat[slot_of].reshape(T, k, d)
    y = jnp.sum(y_slots * top_p[..., None].astype(dtype), axis=1)
    return y, aux


def moe_block(x: Array, lw: dict, cfg: TransformerConfig, sh: ShardingConfig,
              mesh) -> tuple[Array, Array]:
    """x: [B, S, d] -> (y [B, S, d], aux scalar). Expert-parallel shard_map.

    Token parallelism (§Perf H1): the sequence dim is sharded over the
    ``model`` axis too, so each device routes and dispatches only
    ``B_loc * S / ep`` tokens (GShard-style token groups). Without this,
    every model-axis device redundantly dispatches the full local batch —
    16x the activation memory and routing work at mesh width 16.
    """
    moe = cfg.moe
    B, S, d = x.shape
    ep = mesh.shape[sh.model_axis] if mesh is not None else 1
    shard_tokens = ep > 1 and S % ep == 0 and S >= ep

    def body(xl, rw, wg, wu, wd):
        T = xl.shape[0] * xl.shape[1]
        y, aux = _moe_local(
            xl.reshape(T, d), rw, wg, wu, wd,
            moe=moe, model_axis=sh.model_axis, ep=ep, dtype=cfg.dtype,
        )
        axes = tuple(sh.batch_axes) + ((sh.model_axis,) if shard_tokens else ())
        if ep > 1 and axes:
            # aux averaged over every axis that shards tokens; when tokens
            # are NOT model-sharded, the model axis computed identical
            # routing and must not be averaged over.
            aux = jax.lax.pmean(aux, axes)
        return y.reshape(xl.shape), aux

    if mesh is None:  # single-device smoke path
        return body(x, lw["router"], lw["we_gate"], lw["we_up"], lw["we_down"])

    b, m = sh.b, sh.m
    x_spec = P(b, m, None) if shard_tokens else P(b, None, None)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        check_vma=False,
        in_specs=(
            x_spec,  # tokens sharded over batch axes (+ model when possible)
            P(None, None),  # router: replicated
            P(m, None, None),  # experts: EP over model
            P(m, None, None),
            P(m, None, None),
        ),
        out_specs=(x_spec, P()),
    )
    return fn(x, lw["router"], lw["we_gate"], lw["we_up"], lw["we_down"])


def moe_decode_2d(x: Array, lw: dict, cfg: TransformerConfig,
                  sh: ShardingConfig, mesh) -> Array:
    """Decode-path MoE with 2D expert parallelism (§Perf H2).

    The expert weights stay exactly in their storage sharding
    (E over ``model``, d over the FSDP axes) — nothing is gathered. Instead
    the *tokens* move (decode activations are tiny): the token batch is
    all-gathered (<= B x d bytes), dispatched redundantly on every device,
    and each device contributes the partial product of its (E_loc, d_loc)
    weight tile; partials are psum'd over the FSDP axes (gate/up) and the
    expert axis (combine). Replaces a per-layer all-gather of E_loc x d x
    3ff weight bytes (~600 MB/layer for qwen3) with ~2 x E_loc x C x ff
    activation bytes (~6 MB) — the collective-bound -> compute-bound move
    recorded in EXPERIMENTS.md §Perf.

    x: [B, d] sharded over ``sh.cache_batch_axes``; returns same.
    """
    moe = cfg.moe
    B, d = x.shape
    E, k = moe.n_experts, moe.top_k
    m = sh.model_axis
    cb = tuple(sh.cache_batch_axes)
    fs = tuple(sh.batch_axes)  # FSDP axes sharding the weights' d dim
    ep = mesh.shape[m]
    fsz = 1
    for a in fs:
        fsz *= mesh.shape[a]
    E_loc, d_loc = E // ep, d // fsz
    B_loc = B
    for a in cb:
        B_loc //= mesh.shape[a]
    C = max(1, int(math.ceil(moe.capacity_factor * B * k / E)))
    dt = cfg.dtype

    def body(x_loc, rw, wg, wu, wd):
        # 1. full (tiny) token batch everywhere
        x_all = x_loc
        for a in cb:
            x_all = jax.lax.all_gather(x_all, a, axis=0, tiled=True)

        # 2. route + sort-dispatch into [E, C, d] (redundant, cheap at B~128)
        logits = x_all.astype(jnp.float32) @ rw.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
        flat_e = top_e.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        seg_start = jnp.searchsorted(sorted_e, jnp.arange(E))
        pos = jnp.arange(B * k) - seg_start[sorted_e]
        keep = pos < C
        token_of = order // k
        dst = jnp.where(keep, sorted_e * C + pos, E * C)
        xe = jnp.zeros((E * C + 1, d), dt).at[dst].set(
            x_all[token_of].astype(dt), mode="drop")[:E * C].reshape(E, C, d)

        # 3. slice my (E_loc, d_loc) tile of the dispatch buffer
        ei = jax.lax.axis_index(m) * E_loc
        fi = jnp.int32(0)
        for a in fs:
            fi = fi * mesh.shape[a] + jax.lax.axis_index(a)
        xe_loc = jax.lax.dynamic_slice(
            xe, (ei, 0, fi * d_loc), (E_loc, C, d_loc))

        # 4. partial expert ffn; psum over the d-shard (FSDP) axes
        g = jnp.einsum("ecd,edf->ecf", xe_loc, wg.astype(dt))
        u = jnp.einsum("ecd,edf->ecf", xe_loc, wu.astype(dt))
        if fs:
            g = jax.lax.psum(g, fs)
            u = jax.lax.psum(u, fs)
        ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd.astype(dt))
        # ye: [E_loc, C, d_loc] — my experts, my d slice

        # 5. combine: my experts' contribution per token, psum over experts
        slot_of = jnp.zeros((B * k,), jnp.int32).at[order].set(
            jnp.where(keep, dst, E * C).astype(jnp.int32))
        slot = slot_of.reshape(B, k)
        mine = (slot >= ei * C) & (slot < (ei + E_loc) * C)
        local_slot = jnp.clip(slot - ei * C, 0, E_loc * C - 1)
        ye_flat = ye.reshape(E_loc * C, d_loc)
        y_slots = jnp.where(mine[..., None], ye_flat[local_slot], 0.0)
        y_tok = jnp.sum(y_slots * top_p[..., None].astype(dt), axis=1)
        y_tok = jax.lax.psum(y_tok, m)  # [B, d_loc], full B everywhere

        # 6. reassemble [B, d]: each FSDP device owns a disjoint d block
        z = jnp.zeros((B, d), dt)
        z = jax.lax.dynamic_update_slice(z, y_tok.astype(dt), (0, fi * d_loc))
        if fs:
            z = jax.lax.psum(z, fs)
        # 7. back to the local batch shard
        bi = jnp.int32(0)
        for a in cb:
            bi = bi * mesh.shape[a] + jax.lax.axis_index(a)
        return jax.lax.dynamic_slice(z, (bi * B_loc, 0), (B_loc, d))

    cb_spec = tuple(cb) if cb else None
    fs_spec = tuple(fs) if fs else None
    fn = jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(
            P(cb_spec, None),
            P(None, None),
            P(m, fs_spec, None),  # == storage sharding: no weight gather
            P(m, fs_spec, None),
            P(m, None, fs_spec),
        ),
        out_specs=P(cb_spec, None),
    )
    return fn(x, lw["router"], lw["we_gate"], lw["we_up"], lw["we_down"])


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------


def _layer(x, lw, cfg: TransformerConfig, sh: ShardingConfig, mesh, *,
           positions, causal=True, collect_kv=False):
    """One transformer layer (training / prefill path). x: [B, S, d]."""
    B, S, d = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = cfg.dtype

    h = rmsnorm(x, lw["ln1"], cfg.norm_eps)
    q = (h @ lw["wq"].astype(dt)).reshape(B, S, H, hd)
    k = (h @ lw["wk"].astype(dt)).reshape(B, S, KV, hd)
    v = (h @ lw["wv"].astype(dt)).reshape(B, S, KV, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    kv = (k, v) if collect_kv else None
    k = _repeat_kv(k, H // KV)
    v = _repeat_kv(v, H // KV)
    q = _shard(q, P(sh.b, None, sh.m, None))
    k = _shard(k, P(sh.b, None, sh.m, None))
    attn = flash_attention(q, k, v, causal=causal, kv_chunk=cfg.kv_chunk,
                           unroll=cfg.unroll_inner)
    x = x + (attn.reshape(B, S, H * hd) @ lw["wo"].astype(dt))

    h = rmsnorm(x, lw["ln2"], cfg.norm_eps)
    if cfg.moe:
        y, aux = moe_block(h, lw, cfg, sh, mesh)
        if cfg.moe.n_shared:
            y = y + swiglu(
                h, lw["ws_gate"].astype(dt), lw["ws_up"].astype(dt),
                lw["ws_down"].astype(dt),
            )
    else:
        y = swiglu(
            h, lw["w_gate"].astype(dt), lw["w_up"].astype(dt),
            lw["w_down"].astype(dt),
        )
        aux = jnp.float32(0.0)
    x = x + y
    x = _shard(x, P(sh.b, None, None))
    return (x, aux, kv) if collect_kv else (x, aux)


def forward(params, tokens, cfg: TransformerConfig, sh: ShardingConfig,
            mesh=None):
    """tokens [B, S] -> hidden [B, S, d] (+ summed MoE aux loss)."""
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    x = _shard(x, P(sh.b, None, None))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    def inner(x, lw):
        return _layer(x, lw, cfg, sh, mesh, positions=positions)

    if cfg.remat:
        inner = jax.checkpoint(
            inner, policy=jax.checkpoint_policies.nothing_saveable
        )

    if cfg.scan_layers:
        x, auxes = jax.lax.scan(lambda c, lw: inner(c, lw), x,
                                params["layers"])
        aux = jnp.sum(auxes)
    else:  # unrolled (roofline probes)
        aux = jnp.float32(0.0)
        for l in range(cfg.n_layers):
            x, a = inner(x, {k: v[l] for k, v in params["layers"].items()})
            aux = aux + a
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, aux


def chunked_xent(hidden, labels, lm_head, cfg: TransformerConfig):
    """Mean token NLL without materialising [B, S, V]; scans S chunks."""
    B, S, d = hidden.shape
    c = min(cfg.seq_chunk, S)
    if S % c:
        c = S
    n = S // c
    hc = hidden.reshape(B, n, c, d).transpose(1, 0, 2, 3)  # [n, B, c, d]
    lc = labels.reshape(B, n, c).transpose(1, 0, 2)

    V, Vp = cfg.vocab, lm_head.shape[-1]

    @jax.checkpoint
    def one(h, l):
        logits = (h.astype(jnp.float32)) @ lm_head.astype(jnp.float32)
        if Vp > V:  # mask vocab-padding columns out of the softmax
            logits = jnp.where(jnp.arange(Vp) < V, logits, -jnp.inf)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - gold)

    def step(tot, xs):
        h, l = xs
        return tot + one(h, l), None

    tot, _ = jax.lax.scan(step, jnp.float32(0.0), (hc, lc),
                          unroll=n if cfg.unroll_inner else 1)
    return tot / (B * S)


def loss_fn(params, batch, cfg: TransformerConfig, sh: ShardingConfig,
            mesh=None):
    hidden, aux = forward(params, batch["tokens"], cfg, sh, mesh)
    nll = chunked_xent(hidden, batch["labels"], params["lm_head"], cfg)
    aux_w = cfg.moe.aux_loss_weight if cfg.moe else 0.0
    return nll + aux_w * aux, {"nll": nll, "aux": aux}


def prefill_step(params, tokens, cfg: TransformerConfig, sh: ShardingConfig,
                 mesh=None):
    """Inference prefill: process the full prompt, emit the KV cache and the
    last-position logits. tokens [B, S] -> (logits [B, V], cache {k, v} of
    [L, B, S, KV, hd], sequence-sharded per ``sh.cache_seq_axes``)."""
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    x = _shard(x, P(sh.b, None, None))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    cspec = cache_specs(sh)["k"]
    kv_spec = P(cspec[1], cspec[2], cspec[3], cspec[4])  # [B, S, KV, hd]

    def inner(x, lw):
        x, aux, (k, v) = _layer(
            x, lw, cfg, sh, mesh, positions=positions, collect_kv=True
        )
        return x, (_shard(k, kv_spec), _shard(v, kv_spec))

    if cfg.remat:
        inner = jax.checkpoint(
            inner, policy=jax.checkpoint_policies.nothing_saveable
        )

    if cfg.scan_layers:
        x, (k_all, v_all) = jax.lax.scan(inner, x, params["layers"])
    else:
        ks, vs = [], []
        for l in range(cfg.n_layers):
            x, (kl, vl) = inner(x, {k: v[l] for k, v in params["layers"].items()})
            ks.append(kl)
            vs.append(vl)
        k_all = jnp.stack(ks)
        v_all = jnp.stack(vs)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = x[:, -1].astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
    if logits.shape[-1] > cfg.vocab:
        logits = jnp.where(
            jnp.arange(logits.shape[-1]) < cfg.vocab, logits, -jnp.inf
        )
    return logits, dict(k=k_all, v=v_all)


# ---------------------------------------------------------------------------
# Decode (serving) path
# ---------------------------------------------------------------------------


def cache_shapes(cfg: TransformerConfig, batch: int, max_seq: int):
    """KV cache ShapeDtypeStructs: k/v [L, B, S, KV, hd] (+ pos scalar)."""
    s = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.hd)
    return dict(
        k=jax.ShapeDtypeStruct(s, cfg.dtype),
        v=jax.ShapeDtypeStruct(s, cfg.dtype),
    )


def cache_specs(sh: ShardingConfig):
    cb = sh.cache_batch_axes or None
    cs = sh.cache_seq_axes or None
    spec = P(None, tuple(cb) if cb else None, tuple(cs) if cs else None, None, None)
    return dict(k=spec, v=spec)


def decode_step(params, cache, tokens, pos, cfg: TransformerConfig,
                sh: ShardingConfig, mesh=None):
    """One greedy decode step.

    tokens: [B, 1] current token; pos: scalar int32 — current position (the
    cache holds ``pos`` valid entries). Returns (logits [B, V], new_cache).
    The cache S dim is sharded per ``sh.cache_seq_axes``; the softmax /
    weighted-sum reductions over S become GSPMD partial-reductions +
    all-reduce (the distributed flash-decoding LSE merge).
    """
    B = tokens.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = cfg.dtype
    S = cache["k"].shape[2]
    cspec = cache_specs(sh)["k"]

    x = jnp.take(params["embed"], tokens[:, 0], axis=0).astype(dt)  # [B, d]
    positions = jnp.full((B,), pos, jnp.int32)

    def scan_body(carry, xs):
        x, = carry
        lw, kc, vc = xs  # layer weights, k/v cache slabs [B, S, KV, hd]
        h = rmsnorm(x, lw["ln1"], cfg.norm_eps)
        q = (h @ lw["wq"].astype(dt)).reshape(B, 1, H, hd)
        k_new = (h @ lw["wk"].astype(dt)).reshape(B, 1, KV, hd)
        v_new = (h @ lw["wv"].astype(dt)).reshape(B, 1, KV, hd)
        q = rope(q, positions[:, None], cfg.rope_theta)
        k_new = rope(k_new, positions[:, None], cfg.rope_theta)

        kc = jax.lax.dynamic_update_slice(kc, k_new.astype(dt), (0, pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v_new.astype(dt), (0, pos, 0, 0))
        kc = _shard(kc, P(cspec[1], cspec[2], cspec[3], cspec[4]))
        vc = _shard(vc, P(cspec[1], cspec[2], cspec[3], cspec[4]))

        # GQA decode attention over the (sequence-sharded) cache.
        qg = q[:, 0].reshape(B, KV, H // KV, hd).astype(jnp.float32)
        kf = kc.astype(jnp.float32)
        s = jnp.einsum("bkgh,bskh->bkgs", qg, kf) / math.sqrt(hd)  # [B,KV,G,S]
        valid = jnp.arange(S) <= pos
        s = jnp.where(valid[None, None, None, :], s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bkgs,bskh->bkgh", p / jnp.maximum(l, 1e-30),
                       vc.astype(jnp.float32))
        attn = o.reshape(B, H * hd).astype(dt)
        x = x + attn @ lw["wo"].astype(dt)

        h = rmsnorm(x, lw["ln2"], cfg.norm_eps)
        if cfg.moe:
            if mesh is None:
                y, _ = _moe_local_dense(h, lw, cfg)
            else:
                # 2D expert-parallel decode: weights stay in storage
                # sharding, tiny token activations move (§Perf H2).
                y = moe_decode_2d(h, lw, cfg, sh, mesh)
            if cfg.moe.n_shared:
                y = y + swiglu(h, lw["ws_gate"].astype(dt),
                               lw["ws_up"].astype(dt), lw["ws_down"].astype(dt))
        else:
            y = swiglu(h, lw["w_gate"].astype(dt), lw["w_up"].astype(dt),
                       lw["w_down"].astype(dt))
        x = x + y
        return (x,), (kc, vc)

    if cfg.scan_layers:
        (x,), (k_all, v_all) = jax.lax.scan(
            scan_body, (x,), (params["layers"], cache["k"], cache["v"])
        )
    else:
        ks, vs = [], []
        for l in range(cfg.n_layers):
            lw = {k: v[l] for k, v in params["layers"].items()}
            (x,), (kc, vc) = scan_body((x,), (lw, cache["k"][l], cache["v"][l]))
            ks.append(kc)
            vs.append(vc)
        k_all = jnp.stack(ks)
        v_all = jnp.stack(vs)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
    if logits.shape[-1] > cfg.vocab:
        logits = jnp.where(
            jnp.arange(logits.shape[-1]) < cfg.vocab, logits, -jnp.inf
        )
    return logits, dict(k=k_all, v=v_all)


def _moe_local_dense(h, lw, cfg: TransformerConfig):
    """Decode-path MoE: tiny token count, so gather the top-k expert weights
    per token and batch the ffn — no capacity, no drops (T ~ B is small)."""
    moe = cfg.moe
    dt = cfg.dtype
    B, d = h.shape
    logits = h.astype(jnp.float32) @ lw["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, moe.top_k)  # [B, k]
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    wg = lw["we_gate"].astype(dt)[top_e]  # [B, k, d, ff]
    wu = lw["we_up"].astype(dt)[top_e]
    wd = lw["we_down"].astype(dt)[top_e]
    g = jax.nn.silu(jnp.einsum("bd,bkdf->bkf", h, wg))
    u = jnp.einsum("bd,bkdf->bkf", h, wu)
    y = jnp.einsum("bkf,bkfd->bkd", g * u, wd)
    return jnp.sum(y * top_p[..., None].astype(dt), axis=1), jnp.float32(0.0)
