"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (device count is locked on first jax init; the dry-run needs
to set XLA_FLAGS first).

Axes:
  pod    — slow inter-pod links (DCN); gradient sync / pod-DP / PDASC merge
  data   — intra-pod DP + FSDP shard axis + PDASC database shards
  model  — TP (heads/ffn/vocab), EP (experts), sequence sharding for decode,
           embedding-table rows (recsys), PDASC query fan-out
"""

from __future__ import annotations

import jax


def _auto(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devs = jax.devices()
    if len(devs) == n:
        return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))
    # The dry-run process holds 512 placeholder devices; the single-pod mesh
    # uses the first 256.
    from jax.experimental import mesh_utils

    dm = mesh_utils.create_device_mesh(shape, devices=devs[:n])
    return jax.sharding.Mesh(dm, axes, axis_types=_auto(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / small-device runs)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=_auto(len(axes)))


def batch_axes_of(mesh) -> tuple:
    """DP/FSDP axes: every axis except ``model``."""
    return tuple(a for a in mesh.axis_names if a != "model")


def all_axes_of(mesh) -> tuple:
    return tuple(mesh.axis_names)


# TPU v5e hardware constants for the roofline model (per chip).
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link (~4 links usable; we model 1-link worst case)
HBM_BYTES = 16 * 2 ** 30  # 16 GiB
