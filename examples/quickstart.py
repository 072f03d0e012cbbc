"""PDASC quickstart: build a multilevel index, search with arbitrary
distances through the declarative Query API, measure recall against exact
ground truth.

    PYTHONPATH=src python examples/quickstart.py

One index, one query surface: a ``repro.query.Query`` says *what* to
retrieve; ``idx.plan(query)`` binds *how* (which pipeline, which kernel
ops) from the index's capabilities — ``plan.explain()`` shows the choice.

Kernel block sizes default to hand-set per-op tiles. After a one-off
autotune sweep (``PYTHONPATH=src python -m benchmarks.bench_kernels``,
which persists per-shape winners to ``~/.cache/repro/kernel_tune.json``),
pass ``Query(k=10, kernel=ops.KernelConfig(auto=True))`` and every plan
resolves the tuned blocks instead — explicitly-set knobs still win, and
plans re-compile automatically when the cache is retuned (DESIGN.md §3.9).

To serve an index behind the batching engine, see ``examples/serve_ann.py``
/ ``python -m repro.launch.serve``; add ``--replicas 4`` for the replicated
fault-tolerant tier (health-checked replica pool + retry/hedge router,
DESIGN.md §3.10) and ``--faults "wedge:r1@20+8"`` to watch it route around
a deterministically injected fault. For the observability surface add
``--shadow-sample 8`` (online recall estimate with a Wilson interval,
re-answered exactly off the hot path), ``--trace-sample 16`` (the
slowest sampled request's span tree at exit), ``--slo-p99-ms 50`` (multi-rate error-budget burn alerts) and
``--dash`` (live terminal dashboard); ``python -m repro.obs.report
--metrics experiments/serve_metrics.json`` renders a dump offline
(DESIGN.md §3.12).
"""

import numpy as np

from repro.baselines import exact_knn
from repro.core.index import PDASCIndex
from repro.data import make_dataset
from repro.query import Query


def recall(ids, gt):
    k = gt.shape[1]
    return np.mean([
        len(set(ids[i][ids[i] >= 0].tolist()) & set(gt[i].tolist())) / k
        for i in range(len(gt))
    ])


def main():
    # --- a dense-embedding dataset (GLOVE surrogate) -------------------------
    data = make_dataset("dense_embed", n=6000, seed=0)
    train, test = data[:5900], data[5900:5950]

    query = Query(k=10)  # execution="auto": the batched beam hot path
    for distance in ("euclidean", "manhattan", "chebyshev", "cosine"):
        idx = PDASCIndex.build(train, gl=256, distance=distance,
                               radius_quantile=0.35)
        res = idx.plan(query)(test)  # plans cache on the index: re-running
        # an equal query reuses the compiled pipeline, zero retraces
        _, gt = exact_knn(test, train, distance=distance, k=10)
        print(f"{distance:10s} recall@10 = {recall(np.asarray(res.ids), np.asarray(gt)):.3f} "
              f"(mean candidates scanned: {int(np.asarray(res.n_candidates).mean())} "
              f"of {len(train)})")

    # --- the same API on geospatial data with the Haversine metric ----------
    geo = make_dataset("geo_clusters", n=3000, seed=1)
    g_train, g_test = geo[:2900], geo[2900:2950]
    idx = PDASCIndex.build(g_train, gl=60, distance="haversine",
                           radius_quantile=0.5)
    print("\nindex structure (Municipalities surrogate):")
    print(idx.describe())
    plan = idx.plan(Query(k=10, execution="dense"))  # the faithful pipeline
    print("\nwhat the planner bound (plan.explain()):")
    print(plan.explain())
    res = plan(g_test)
    _, gt = exact_knn(g_test, g_train, distance="haversine", k=10)
    print(f"\nhaversine  recall@10 = {recall(np.asarray(res.ids), np.asarray(gt)):.3f}")

    # --- non-metric dissimilarity (paper future work: Jaccard) --------------
    # (weighted Jaccard on the MNIST-like surrogate: overlapping supports —
    # on near-disjoint tf-idf vectors the prototype frontier saturates at
    # d=1.0 and prunes structurally, a known Jaccard-on-sparse caveat)
    docs = np.abs(make_dataset("sparse_highdim", n=3000, seed=2))
    d_train, d_test = docs[:2900], docs[2900:2950]
    idx = PDASCIndex.build(d_train, gl=128, distance="jaccard",
                           radius_quantile=0.6)
    res = idx.plan(Query(k=10, execution="dense"))(d_test)
    _, gt = exact_knn(d_test, d_train, distance="jaccard", k=10)
    rec = recall(np.asarray(res.ids), np.asarray(gt))
    print(f"jaccard    recall@10 = {rec:.3f}")

    # --- observability tour (DESIGN.md §3.11/§3.12) -------------------------
    # Everything above also reported into the process-wide repro.obs
    # registry; recall@k is k Bernoulli trials per query, so an estimate
    # over a sample carries a Wilson score interval (what the serving
    # tier's --shadow-sample online estimator publishes live).
    from repro import obs

    trials = int(np.asarray(gt).size)
    lo, hi = obs.wilson(rec * trials, trials)
    print(f"           95% Wilson interval over {trials} trials: "
          f"[{lo:.3f}, {hi:.3f}]")
    snap = obs.snapshot()
    print("\nplan executions by pipeline (obs.snapshot()):")
    for row in snap[obs.names.PLAN_EXECUTIONS]["series"]:
        print(f"  {row['labels']}: {int(row['value'])}")


if __name__ == "__main__":
    main()
