"""Chip smoke run: PDASC's main path end to end on a TPU at the paper's shape.

    python chip_smoke.py              # one chip: build -> beam / two_stage serving
    python chip_smoke.py --chips 4    # one four-chip host: sharded build + search

One chip: a PDASC index over ``dense_embed`` at the ``PDASCArchConfig``
shape (n = 2^20 points, d = 100, euclidean, gl = 1024, int8 payload tier) is
built with ``PDASCIndex.build``; 256 queries are served through
``QueryHandler`` + ``BatchingEngine`` (``repro.launch.serve.serve_engine``)
in ``beam`` (beam 32) and ``two_stage`` (int8 scan, exact rerank of 128)
execution; exact kNN runs on the chip through the fused Pallas kernel.

Four chips: one sub-index per chip over n = 2^22 (``build_sharded``), beam
search through ``compile_sharded_plan`` with the butterfly top-k merge,
compared with ``exact_knn_sharded``.

Every answer is checked against a float64 NumPy brute force on the host:
(a) the chip's exact kNN ids agree on >= 0.99 of slots (ties count);
(b) every returned distance is within 1e-3 relative of the float64 distance
of its id, lists ascend, ids are unique and in range;
(c) one chip only: two_stage recall@10 is within 0.02 of beam recall@10.
Any failed phase raises (traceback, non-zero exit). The last line of
standard output is the JSON result, printed only when every check passed.
There is no CPU fallback: without a TPU the script exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

N_QUERIES = 256
N_TEST = 4096  # held-out rows the queries are drawn from
AGREE_MIN = 0.99  # check (a)
DIST_RTOL = 1e-3  # check (b)
RECALL_GAP = 0.02  # check (c)


def _parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4))
    p.add_argument("--log2n", type=int, default=None,
                   help="database size per chip as a power of two "
                        "(default: 20, the PDASCArchConfig shape)")
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# -- float64 host reference --------------------------------------------------


def exact_f64(X, Q, k):
    """Brute-force float64 kNN: (dists [q, k] ascending, ids [q, k])."""
    X = np.asarray(X, np.float64)
    xx = np.einsum("nd,nd->n", X, X)
    out_d, out_i = [], []
    for s in range(0, len(Q), 32):
        q = np.asarray(Q[s:s + 32], np.float64)
        d2 = xx[None, :] - 2.0 * (q @ X.T) + np.einsum("qd,qd->q", q, q)[:, None]
        part = np.argpartition(d2, k, axis=1)[:, :k]
        order = np.argsort(np.take_along_axis(d2, part, 1), axis=1)
        ids = np.take_along_axis(part, order, 1)
        out_i.append(ids)
        # distances of the chosen ids by direct subtraction (no cancellation)
        out_d.append(np.linalg.norm(X[ids] - q[:, None, :], axis=-1))
    return np.concatenate(out_d), np.concatenate(out_i)


def dist_f64(X, Q, ids):
    """Float64 distance of each returned id ([q, k]; NaN where id < 0)."""
    safe = np.clip(ids, 0, len(X) - 1)
    d = np.linalg.norm(np.asarray(X, np.float64)[safe]
                       - np.asarray(Q, np.float64)[:, None, :], axis=-1)
    return np.where(ids >= 0, d, np.nan)


def agreement(X, Q, ids, ref_d):
    """Share of the [q, k] slots holding a true k-nearest neighbour: an id
    counts when its float64 distance is within the float64 k-th distance
    (so a tie at the k-th place counts as agreement)."""
    d = dist_f64(X, Q, ids)
    kth = ref_d[:, -1:]
    ok = (ids >= 0) & (d <= kth * (1 + 1e-6) + 1e-9)
    return float(ok.mean())


def check_results(name, X, Q, dists, ids):
    """Check (b): distances, order, uniqueness and range of an answer."""
    n = len(X)
    dists = np.asarray(dists, np.float64)
    ids = np.asarray(ids)
    live = ids >= 0
    if not live.any():
        raise AssertionError(f"{name}: no ids returned")
    if (ids[live] >= n).any():
        raise AssertionError(f"{name}: ids out of range [0, {n})")
    for row in ids:
        r = row[row >= 0]
        if len(np.unique(r)) != len(r):
            raise AssertionError(f"{name}: duplicate ids in a result row")
    ref = dist_f64(X, Q, ids)
    err = np.abs(dists - ref)[live]
    bad = err > DIST_RTOL * ref[live] + 1e-6
    if bad.any():
        raise AssertionError(
            f"{name}: {int(bad.sum())} distances off the float64 distance "
            f"by > {DIST_RTOL} relative (max abs err {err.max():.3g})")
    masked = np.where(live, dists, np.inf)
    if (np.diff(masked, axis=1) < 0).any():
        raise AssertionError(f"{name}: result lists are not ascending")
    return float(np.max(err / np.maximum(ref[live], 1e-12))), int((~live).sum())


# -- phases ------------------------------------------------------------------


def one_chip(args):
    from repro.configs.pdasc import PDASCArchConfig
    from repro.core.index import PDASCIndex
    from repro.data import make_dataset
    from repro.kernels.ops import KernelConfig
    from repro.launch import serve

    cfg = PDASCArchConfig()
    log2n = args.log2n or cfg.n.bit_length() - 1
    n = 1 << log2n
    if n < cfg.n:
        _log(f"SIZE CUT: n = 2^{log2n} = {n} (config: {cfg.n})")
    t0 = time.time()
    data = make_dataset("dense_embed", n=n + N_TEST, seed=args.seed)
    train, test = data[:n], data[n:]
    _log(f"data: train {train.shape} test {test.shape} "
         f"({time.time() - t0:.1f}s)")

    t0 = time.time()
    idx = PDASCIndex.build(
        train, gl=cfg.gl, distance=cfg.distance, radius_quantile=0.5,
        row_chunk=cfg.row_chunk, group_chunk=cfg.group_chunk,
        swap_tol=cfg.swap_tol, bg=cfg.bg, store=cfg.store,
        store_block=cfg.store_block,
    )
    jax.block_until_ready(idx.data)
    build_s = time.time() - t0
    _log(f"build_s={build_s:.1f} (compile included) levels={idx.n_levels} "
         f"radius={idx.default_radius:.4f}")
    _log(f"memory_bytes={idx.memory_bytes()}")

    # Tuner cache off: no state from outside the checkout steers the blocks.
    kernel = KernelConfig(auto=False)
    results = {}
    for mode in ("beam", "two_stage"):
        sargs = serve.parse_args([
            "--mode", mode, "--k", str(cfg.k), "--beam", "32",
            "--rerank-width", str(cfg.rerank_width), "--queries",
            str(N_QUERIES), "--batch", "32", "--seed", str(args.seed),
            "--distance", cfg.distance,
        ])
        t0 = time.time()
        res = serve.serve_engine(sargs, idx, kernel, train, test)
        res["wall_s"] = time.time() - t0
        results[mode] = res

    q_rows = results["beam"]["q_rows"]
    if not (q_rows == results["two_stage"]["q_rows"]).all():
        raise AssertionError("beam and two_stage served different queries")
    Q = test[q_rows]
    t0 = time.time()
    ref_d, _ = exact_f64(train, Q, cfg.k)
    _log(f"float64 host reference: {time.time() - t0:.1f}s")

    # (a) the chip's exact kNN (ops.knn -> knn_pallas) against float64
    agree = agreement(train, Q, results["beam"]["gt"], ref_d)
    _log(f"check (a) exact kNN on chip vs float64: agreement={agree:.4f} "
         f"(need >= {AGREE_MIN})")
    if agree < AGREE_MIN:
        raise AssertionError(f"exact kNN agreement {agree:.4f} < {AGREE_MIN}")

    recall = {}
    for mode, res in results.items():
        max_rel, missing = check_results(mode, train, Q, res["dists"],
                                         res["ids"])
        recall[mode] = agreement(train, Q, res["ids"], ref_d)
        _log(f"{mode}: warmup_s={res['warmup_s']:.1f} (compile included) "
             f"p50_ms={res['p50_ms']:.2f} p99_ms={res['p99_ms']:.2f} "
             f"recall@{cfg.k}={recall[mode]:.4f} (float64 reference) "
             f"max_rel_dist_err={max_rel:.2e} missing_slots={missing} "
             f"wall_s={res['wall_s']:.1f}")
    _log("check (b) distances / order / ids: passed for beam and two_stage")
    gap = abs(recall["two_stage"] - recall["beam"])
    _log(f"check (c) |recall two_stage - beam| = {gap:.4f} "
         f"(need <= {RECALL_GAP})")
    if gap > RECALL_GAP:
        raise AssertionError(f"two_stage recall off beam by {gap:.4f}")


def _placement(devices, *, shard_bytes: int) -> None:
    """Print each device's ``bytes_in_use``; every chip must hold at least
    its own shard of the database (no shard piled onto device 0)."""
    for d in devices:
        stats = d.memory_stats()  # None on backends without allocator stats
        used = stats["bytes_in_use"] if stats else None
        _log(f"device {d.id} bytes_in_use={used}")
        if used is not None and used < shard_bytes:
            raise AssertionError(f"device {d.id} holds {used} bytes, less "
                                 f"than one shard ({shard_bytes})")


def four_chips(args, devices):
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.pdasc import PDASCArchConfig
    from repro.core import distributed as dd
    from repro.core import msa
    from repro.core import radius as radius_lib
    from repro.data import make_dataset
    from repro.kernels.ops import KernelConfig
    from repro.launch.mesh import make_mesh
    from repro.query import Query, compile_sharded_plan

    cfg = PDASCArchConfig()
    log2n = args.log2n or cfg.n.bit_length() - 1
    per = 1 << log2n
    n = per * len(devices)
    if per < cfg.n:
        _log(f"SIZE CUT: 2^{log2n} = {per} points per chip (config: {cfg.n})")
    mesh = make_mesh((len(devices),), ("data",))
    t0 = time.time()
    data = make_dataset("dense_embed", n=n + N_TEST, seed=args.seed)
    train, test = data[:n], data[n:]
    rng = np.random.default_rng(args.seed)
    Q = test[rng.integers(0, len(test), N_QUERIES)]
    db = jax.device_put(train, NamedSharding(mesh, P("data", None)))
    _log(f"data: train {train.shape} over {len(devices)} chips "
         f"({time.time() - t0:.1f}s)")

    t0 = time.time()
    sidx = dd.build_sharded(
        db, mesh, db_axes=("data",), gl=cfg.gl, distance=cfg.distance,
        row_chunk=cfg.row_chunk, group_chunk=cfg.group_chunk,
        swap_tol=cfg.swap_tol, bg=cfg.bg,
    )
    jax.block_until_ready(sidx)
    _log(f"build_sharded_s={time.time() - t0:.1f} (compile included) "
         f"levels={len(sidx.levels)}")
    _placement(devices, shard_bytes=per * train.shape[1] * 4)

    r = radius_lib.estimate_radius(jnp.asarray(train[:per]), cfg.distance,
                                   quantile=0.5)
    plan = compile_sharded_plan(
        mesh, Query(k=cfg.k, radius=r, execution="beam", beam=32,
                    kernel=KernelConfig(auto=False)),
        dist=cfg.distance, db_axes=("data",),
        max_children=msa.max_children(sidx),
    )
    Qd = jnp.asarray(Q)
    t0 = time.time()
    res = plan(sidx, Qd)
    jax.block_until_ready(res)
    _log(f"sharded beam search: first call {time.time() - t0:.1f}s "
         f"(compile included)")
    t0 = time.time()
    res = plan(sidx, Qd)
    jax.block_until_ready(res)
    _log(f"sharded beam search: {N_QUERIES} queries in "
         f"{(time.time() - t0) * 1e3:.1f}ms")
    t0 = time.time()
    gd, gi = dd.exact_knn_sharded(db, Qd, mesh, db_axes=("data",),
                                  distance="l2", k=cfg.k)
    jax.block_until_ready(gi)
    _log(f"exact_knn_sharded: {time.time() - t0:.1f}s (compile included)")

    t0 = time.time()
    ref_d, _ = exact_f64(train, Q, cfg.k)
    _log(f"float64 host reference: {time.time() - t0:.1f}s")
    agree = agreement(train, Q, np.asarray(gi), ref_d)
    _log(f"check (a) exact_knn_sharded vs float64: agreement={agree:.4f} "
         f"(need >= {AGREE_MIN})")
    if agree < AGREE_MIN:
        raise AssertionError(f"exact kNN agreement {agree:.4f} < {AGREE_MIN}")
    check_results("exact_knn_sharded", train, Q, gd, gi)
    max_rel, missing = check_results("sharded beam", train, Q, res.dists,
                                     res.ids)
    recall = agreement(train, Q, np.asarray(res.ids), ref_d)
    _log(f"sharded beam: recall@{cfg.k}={recall:.4f} (float64 reference) "
         f"max_rel_dist_err={max_rel:.2e} missing_slots={missing}")
    _log("check (b) distances / order / ids: passed")
    _placement(devices, shard_bytes=per * train.shape[1] * 4)


def main(argv=None) -> int:
    args = _parse(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.launch import compile_cache
    except ImportError as e:
        print(f"[chip_smoke] the repro package is not beside this script "
              f"({e}); run it from a checkout of the repository",
              file=sys.stderr)
        return 2
    _log(f"compile cache: {compile_cache.enable()}")

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX sees {devices[0].platform} devices "
              f"only; this check runs on the chip and has no CPU fallback",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"[chip_smoke] --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:args.chips]
    _log(f"device: {devices[0].device_kind} x{len(devices)} "
         f"(jax {jax.__version__})")
    if args.chips == 1:
        one_chip(args)
    else:
        four_chips(args, devices)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
