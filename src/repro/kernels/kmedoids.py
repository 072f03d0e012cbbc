"""Fused FasterPAM swap-sweep Pallas kernel (the MSA build hot spot).

Each k-medoids swap sweep evaluates every (medoid slot i, candidate j) swap
delta ``dTD[i, j] = S[j] + T[i, j]`` (see ``ref.swap_deltas_ref`` for the
contract). Done naively that materialises two ``[g, g]`` intermediates — the
shared-gain matrix and the removal-term matrix — per group, on top of the
``[g, g]`` dissimilarities already resident. At ``gl = 1024`` that is 12 MB
of f32 traffic per group per sweep, all of it HBM-bound on TPU.

This kernel streams the sweep instead:

  grid = (g / bg,)            # row (point) axis sequential ("arbitrary")
  per step, VMEM only:
    d    = D[o_tile, :]                               [bg, g]   input block
    gain = min(d - d1, 0) * valid                     [bg, g]   VMEM tile
    t    = where(d >= d1, min(d2, d) - d1, 0) * valid [bg, g]   VMEM tile
    onehot(n1_tile)                                   [bg, k]   iota compare
    acc += onehot^T @ t + sum(gain, rows)             [k, g]    output ref

The one-hot contraction is an MXU matmul; the ``S`` row sum is linear across
row tiles so its partial contribution is broadcast onto every slot row as it
streams. The only persistent state is the ``[k, g]`` ΔTD accumulator living
in the revisited output block — the ``[g, g]`` gain / removal matrices never
exist, in VMEM or HBM.

The FasterPAM caches ``d1/d2/n1`` and the validity mask ride along as
``[bg, 1]`` column blocks. Padded rows carry ``valid = 0`` and contribute
nothing; padded columns and slots are sliced off by the wrapper (callers mask
invalid columns anyway before taking argmins — ``core.kmedoids``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling
from repro.kernels.ref import PRECISION

Array = jax.Array


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _sweep_kernel(d_ref, d1_ref, d2_ref, n1_ref, v_ref, o_ref, *, kp):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    d = d_ref[...].astype(jnp.float32)  # [bg, gp]
    d1 = d1_ref[...].astype(jnp.float32)  # [bg, 1]
    d2 = d2_ref[...].astype(jnp.float32)  # [bg, 1]
    vf = v_ref[...].astype(jnp.float32)  # [bg, 1]
    bg = d.shape[0]

    gain = jnp.minimum(d - d1, 0.0) * vf  # [bg, gp]
    t = jnp.where(d >= d1, jnp.minimum(d2, d) - d1, 0.0) * vf  # [bg, gp]

    slots = jax.lax.broadcasted_iota(jnp.int32, (bg, kp), 1)
    onehot = jnp.where(slots == n1_ref[...], vf, 0.0)  # [bg, kp]

    # T contribution (MXU) + this tile's S partial broadcast onto every slot.
    o_ref[...] += (
        jnp.dot(onehot.T, t, preferred_element_type=jnp.float32,
                precision=PRECISION)
        + jnp.sum(gain, axis=0, keepdims=True)
    )


@functools.partial(jax.jit, static_argnames=("k", "bg", "interpret"))
def swap_deltas_pallas(
    D: Array,
    d1: Array,
    d2: Array,
    n1: Array,
    valid: Array,
    *,
    k: int,
    bg: int = 128,
    interpret: bool = False,
) -> Array:
    """Streamed swap-sweep ΔTD: ``[g, g]`` + caches -> ``[k, g]``.

    Pads the point axis to a ``bg`` multiple, the candidate axis to the lane
    width and the slot axis to the sublane width; the result is sliced back
    to ``[k, g]``. Matches ``ref.swap_deltas_ref`` element-for-element.
    """
    g = D.shape[0]
    if D.shape != (g, g):
        raise ValueError(f"D must be square, got {D.shape}")
    # Backend-real tiling: shrink a row tile overhanging the point axis and
    # halve it until the [bg, gc] gain/removal tiles fit the VMEM budget.
    bg = tiling.shrink(bg, g, tiling.sublane(jnp.float32))
    bg = tiling.fit_budget(
        bg, lambda x: tiling.vmem_swap(x, g, k), floor=min(bg, 8)
    )
    gr = _ceil_to(g, bg)  # row (point) axis
    gc = _ceil_to(g, 128)  # candidate axis (lane width)
    kp = _ceil_to(k, 8)  # slot axis (f32 sublane width)

    Dp = jnp.pad(D.astype(jnp.float32), ((0, gr - g), (0, gc - g)))
    col = lambda x, dt: jnp.pad(x.astype(dt), (0, gr - g)).reshape(gr, 1)
    d1p = col(d1, jnp.float32)
    d2p = col(d2, jnp.float32)
    n1p = col(n1, jnp.int32)
    vp = col(valid, jnp.float32)

    out = pl.pallas_call(
        functools.partial(_sweep_kernel, kp=kp),
        grid=(gr // bg,),
        in_specs=[
            pl.BlockSpec((bg, gc), lambda i: (i, 0)),
            pl.BlockSpec((bg, 1), lambda i: (i, 0)),
            pl.BlockSpec((bg, 1), lambda i: (i, 0)),
            pl.BlockSpec((bg, 1), lambda i: (i, 0)),
            pl.BlockSpec((bg, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((kp, gc), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((kp, gc), jnp.float32),
        interpret=interpret,
        name="swap_deltas_pallas",
    )(Dp, d1p, d2p, n1p, vp)
    return out[:k, :g]
