"""Explicit data-parallel step with inter-pod gradient compression.

The GSPMD train steps sync gradients implicitly (psum inserted by XLA). At
multi-pod scale the ``pod`` axis crosses DCN (~25x slower than ICI), so this
module provides the explicit alternative the launcher can select:

    shard_map over (pod, data):
      local grads                      (per device)
      psum over 'data'                 (fast ICI, full precision)
      compress -> psum over 'pod' -> decompress   (slow DCN, compressed)
      error feedback state carried in the optimizer loop

Compression: magnitude top-k with error feedback (``repro.optim.compression``)
— wire bytes drop by n/k (e.g. 100x at 1%) on the slow axis only, with the
compression error re-injected next step. PowerSGD is available for 2D
tensors. EXPERIMENTS.md §Perf quantifies the inter-pod byte reduction.

This module targets pure-DP workloads (every param replicated across the DP
axes — the recsys/gnn regime; LM tensor-parallel params would compress per
shard the same way).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.optim import adamw as opt_lib
from repro.optim import compression as comp


def make_compressed_dp_step(
    loss_fn: Callable,  # (params, batch) -> (loss, aux)
    mesh,
    opt_cfg: opt_lib.AdamWConfig,
    *,
    data_axis: str = "data",
    pod_axis: str = "pod",
    compress_ratio: float = 0.01,
):
    """Returns (step_fn, init_comp_state).

    step_fn(params, opt_state, comp_state, batch) ->
        (params, opt_state, comp_state, metrics)

    ``batch`` arrays are sharded over (pod, data) on axis 0; params are
    replicated.
    """

    def _k_of(g):
        return max(1, int(g.size * compress_ratio))

    def init_comp_state(params):
        return jax.tree.map(lambda p: comp.topk_init(p).error, params)

    def body(params, opt_state, errors, batch):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch
        )
        # fast axis: full-precision psum (ICI)
        grads = jax.lax.pmean(grads, data_axis)

        # slow axis: top-k compress -> psum -> decompress, with error feedback
        def one(g, err):
            flat = g.astype(jnp.float32).reshape(-1) + err.reshape(-1)
            k = _k_of(g)
            _, idx = jax.lax.top_k(jnp.abs(flat), k)
            vals = flat[idx]
            kept = jnp.zeros_like(flat).at[idx].set(vals)
            new_err = (flat - kept).reshape(g.shape)
            # dense-decompressed psum keeps semantics identical to sending
            # (vals, idx) pairs over DCN; wire bytes counted = 8k vs 4n.
            summed = jax.lax.pmean(kept, pod_axis)
            return summed.reshape(g.shape).astype(g.dtype), new_err

        flat_g, tdef = jax.tree.flatten(grads)
        flat_e = tdef.flatten_up_to(errors)
        pairs = [one(g, e) for g, e in zip(flat_g, flat_e)]
        grads = tdef.unflatten([p[0] for p in pairs])
        errors = tdef.unflatten([p[1] for p in pairs])

        new_p, new_o, m = opt_lib.adamw_update(grads, opt_state, params, opt_cfg)
        loss = jax.lax.pmean(jax.lax.pmean(loss, data_axis), pod_axis)
        return new_p, new_o, errors, {"loss": loss, **m}

    rep = P()

    def step(params, opt_state, comp_state, batch):
        batch_specs = jax.tree.map(
            lambda x: P((pod_axis, data_axis), *([None] * (x.ndim - 1))), batch
        )
        rep_tree = lambda t: jax.tree.map(lambda _: rep, t)
        fn = jax.shard_map(
            body, mesh=mesh, check_vma=False,
            in_specs=(rep_tree(params), rep_tree(opt_state),
                      rep_tree(comp_state), batch_specs),
            out_specs=(rep_tree(params), rep_tree(opt_state),
                       rep_tree(comp_state), {"loss": rep, "grad_norm": rep,
                                              "lr": rep}),
        )
        return fn(params, opt_state, comp_state, batch)

    # jitted: run eagerly, the shard_map re-traces its body on every step
    return jax.jit(step), init_comp_state
