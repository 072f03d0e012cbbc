"""Backend-real tile geometry for the Pallas kernels (DESIGN.md §3.9).

The TPU vector layout packs (sublane, lane) tiles whose minimum shape
depends on dtype — (8, 128) for f32, (16, 128) for bf16/f16, (32, 128) for
int8 — and every kernel tile lives in ~16 MB of VMEM per core. The kernel
wrappers used to hard-code 128/256 block defaults regardless of dtype or
problem shape; this module centralises the geometry so each wrapper can

* align block sizes to the dtype's (sublane, lane) multiples,
* shrink blocks that overhang the (padded) problem shape — a 128-row tile
  over an 8-row input is 16x padding waste, and
* bound per-step VMEM footprints by halving the streaming axis instead of
  a fixed magic clamp.

The same helpers drive the autotuner (``kernels/autotune.py``): candidate
grids are generated on these multiples, pruned by the VMEM estimators, and
scored with :func:`pad_waste` so ragged shapes penalise overhanging tiles.
"""

from __future__ import annotations

import numpy as np

LANE = 128  # minor-axis vector width (all dtypes)
VMEM_BUDGET = 8 * 2 ** 20  # conservative per-kernel-step budget (~half VMEM)

# Per-op hand-set default block sizes (the pre-autotuner behaviour; also the
# grid member every sweep must contain so the tuned winner can never lose to
# the default by construction). ``ops`` falls back to these when no
# KernelConfig is threaded.
OP_DEFAULTS = {
    "pairwise": dict(bm=128, bn=128, bd=256),
    "knn": dict(bq=128, bn=512),
    "rank": dict(bq=8, bn=256),
    "scan": dict(bq=8, bn=256),
    "swap": dict(bg=128),
}


def ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def sublane(dtype) -> int:
    """Minimum second-minor tile extent for ``dtype`` (f32 8, bf16 16, int8 32)."""
    size = np.dtype(dtype).itemsize
    return {4: 8, 2: 16, 1: 32}.get(size, 8)


def shrink(block: int, extent: int, mult: int) -> int:
    """Shrink-only fit of a block to a problem axis.

    Returns ``min(block, ceil_to(extent, mult))`` — a block larger than the
    axis (rounded up to its hardware multiple) only pads; a caller's smaller
    explicit block is never enlarged, so test-sized knobs pass through.
    """
    return max(1, min(block, ceil_to(max(extent, 1), mult)))


def fit_budget(block: int, step_bytes, *, floor: int, budget: int = VMEM_BUDGET) -> int:
    """Halve ``block`` until ``step_bytes(block) <= budget`` (or the floor).

    ``step_bytes``: callable mapping a candidate block to the per-grid-step
    VMEM footprint in bytes. Used for the streaming axis of each kernel
    (``bd`` of the VPU cube, ``bn`` of the rank/scan candidate cube).
    """
    while block > floor and step_bytes(block) > budget:
        block = max(floor, block // 2)
    return block


def pad_waste(shape, blocks) -> float:
    """Fractional padded-compute overhead of gridding ``shape`` by ``blocks``.

    ``prod(ceil_to(s, b)) / prod(s) - 1``: 0.0 for exact fits, 15.0 for a
    128-tile over an 8-row axis. The autotuner multiplies measured time by
    ``(1 + pad_waste)``-normalised scores so a tile that only wins because
    the timing shape happened to fit it exactly does not get cached for the
    whole shape bucket.
    """
    real, padded = 1.0, 1.0
    for s, b in zip(shape, blocks):
        s = max(int(s), 1)
        real *= s
        padded *= ceil_to(s, max(int(b), 1))
    return padded / real - 1.0


# -- per-op VMEM estimators (bytes per grid step) ---------------------------


def vmem_pairwise(form: str, bm: int, bn: int, bd: int, itemsize: int = 4) -> int:
    """Gram: two input tiles + f32 scratch/out; VPU adds the [bm,bn,bd] cube.

    ``bd`` is the lane axis of both input tiles, so it stays a multiple of
    128 (or the whole padded ``d``); the wrappers fit the budget by shrinking
    ``bm`` / ``bn`` instead.
    """
    tiles = (bm + bn) * bd * itemsize + 3 * bm * bn * 4
    if form in ("l1", "chebyshev"):
        tiles += bm * bn * ceil_to(bd, LANE) * 4
    return tiles


def vmem_knn(bq: int, bn: int, d: int, k: int, itemsize: int = 4) -> int:
    return (bq + bn) * d * itemsize + 3 * bq * (k + bn) * 4


def vmem_rank(bq: int, bn: int, d: int, k: int, itemsize: int = 4,
              planes: int = 1) -> int:
    """Candidate cube in native dtype + an f32 copy per unpacked plane.

    ``d`` is the stored row width (the packed width for int4 / binary
    codes), padded to whole 128-lane tiles as VMEM holds it; ``planes`` is
    the number of f32 feature planes the kernel unpacks each row into
    (``quantized.PLANES``).
    """
    dl = ceil_to(d, LANE)
    return (bq * bn * dl * (itemsize + 4 * planes) + bq * dl * 4 * planes
            + 3 * bq * (k + bn) * 4)


RUNS_GROUP = 8  # queries per grid step of the child-run rank (f32 sublanes)


def vmem_rank_runs(d: int) -> int:
    """Child-run rank of f32 rows: two double-buffered [d, 128] table blocks
    and one double-buffered lane-broadcast [d, 128] query per query of the
    group, and the [d, 256] working set of one run."""
    block = ceil_to(d, 8) * LANE * 4
    return 2 * 3 * RUNS_GROUP * block + 4 * 2 * block


def vmem_swap(bg: int, g: int, k: int) -> int:
    gc = ceil_to(g, LANE)
    return 3 * bg * gc * 4 + 2 * ceil_to(k, 8) * gc * 4
