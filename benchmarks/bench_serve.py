"""Replicated serving-tier benchmark: tail latency + saturation under
injected faults (DESIGN.md §3.10).

Drives open-loop Poisson query traffic at a replicated PDASC serving tier
(``serving.ReplicaSet`` behind the retry/hedge/backoff ``Router``) and
records, into ``BENCH_serve.json``:

  * saturation QPS (closed-loop, all workers pinned) per scenario,
  * open-loop p50/p99/p999 latency at ~0.6x saturation,
  * caller-visible errors (the acceptance bar: ZERO, faulted or not),
  * router activity: retries, hedges, degraded serves, health events.

A third ``telemetry`` scenario (DESIGN.md §3.11) serves a store-backed
``two_stage`` tier with 1-in-4 request tracing and records the full
``repro.obs`` metrics snapshot, a p99 exemplar span tree, and the measured
instrumentation overhead (``--smoke`` asserts non-zero engine/router/store
series, a complete exemplar trace, and overhead ratio >= 0.95).

A fourth ``quality`` scenario (DESIGN.md §3.12) adds shadow recall
sampling and SLO burn alerts on the same tier, asserting: online recall
within +-0.05 of the offline recall over the same served queries; >= 1
SLO burn alert under an injected wedge and zero fault-free; and
instrumented + shadow-sampled throughput >= 0.93x uninstrumented. The
run always leaves ``experiments/serve_metrics.json`` behind for
``python -m repro.obs.report`` (the CI offline-report contract).

Scenarios: ``fault_free``, and ``wedged`` — a deterministic ``FaultPlan``
wedges 1 of 4 replicas mid-run (its batch handler stalls per dispatch).
The router must route around it: hedges rescue the stalled requests,
consecutive failures eject the replica, and once the wedge window passes a
half-open probe readmits it. Asserted here (smoke and full):

  * zero caller-visible errors in every scenario,
  * the faulted run's event log shows ``eject`` AND ``readmit``,
  * (full only) faulted p99 within 3x of fault-free p99.

    PYTHONPATH=src python -m benchmarks.bench_serve [--smoke]
        [--out experiments/serve.json] [--bench-out BENCH_serve.json]

``--smoke`` runs a tiny config (correctness + fault-recovery assertions
only, no saturation sweep) so CI catches serving-tier regressions after
``bench_kernels``, matching the other ``--smoke`` benches.
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time

import numpy as np

from repro import obs
from repro.obs import names as mnames
from repro.core.index import PDASCIndex
from repro.data import make_dataset
from repro.query import Query, degraded
from repro.serving import FaultPlan, ReplicaSet, Router, RouterConfig

N_REPLICAS = 4


def _build(smoke: bool, seed: int):
    if smoke:
        n, n_queries, gl = 1200, 256, 64
    else:
        n, n_queries, gl = 7800, 512, 256
    data = make_dataset("dense_embed", n=n + n_queries, seed=seed)
    train, test = data[:n], data[n:n + n_queries]
    idx = PDASCIndex.build(train, gl=gl, distance="euclidean",
                           radius_quantile=0.35)
    return idx, test, dict(dataset="dense_embed", n=n, gl=gl,
                           n_queries=n_queries, distance="euclidean")


def _make_tier(idx, query, fault_plan, seed):
    rs = ReplicaSet(
        idx, query, n_replicas=N_REPLICAS, batch_size=8, max_wait_ms=1.0,
        degraded_query=degraded(query), fault_plan=fault_plan,
    )
    router = Router(rs, RouterConfig(
        deadline_s=5.0, max_retries=2, hedge=True, hedge_min_s=0.02,
        eject_failures=2, probe_cooldown_s=0.1, probe_timeout_s=0.25,
        probe_interval_s=0.02, seed=seed,
    ))
    # Warm every replica's engine (they share the jitted executables, but
    # each engine must see one batch so the bench never times a compile).
    warm = [r.submit(r.probe_payload()) for r in rs.replicas]
    for req in warm:
        req.wait(timeout=300)
    return rs, router


def _closed_loop_qps(router, test, *, workers=8, per_worker=40):
    """Saturation throughput: every worker pinned in a search loop."""
    errors = [0] * workers

    def worker(w):
        rng = np.random.default_rng(w)
        for _ in range(per_worker):
            try:
                router.search(test[rng.integers(len(test))])
            except Exception:  # noqa: BLE001 — counted below
                errors[w] += 1

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(workers)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    return workers * per_worker / elapsed, sum(errors)


def _open_loop(router, test, *, qps: float, n: int, seed: int):
    """Open-loop Poisson arrivals at ``qps``: the dispatcher never waits
    for a response before the next arrival (each request runs its own
    waiter thread — the router's retry/hedge state machine is driven from
    the waiting caller), so queueing delay shows up in the latencies
    instead of silently throttling the offered load."""
    rng = np.random.default_rng(seed)
    order = rng.integers(0, len(test), n)
    gaps = rng.exponential(1.0 / qps, n)
    lats, errors = [], []
    lock = threading.Lock()
    retries = [0]
    hedges = [0]
    degraded_n = [0]

    def fire(i):
        try:
            res = router.search(test[order[i]])
        except Exception as e:  # noqa: BLE001 — the acceptance counter
            with lock:
                errors.append(type(e).__name__)
            return
        with lock:
            lats.append(res.latency_s)
            retries[0] += res.retries
            hedges[0] += int(res.hedged)
            degraded_n[0] += int(res.degraded)

    threads = []
    next_at = time.perf_counter()
    for i in range(n):
        next_at += gaps[i]
        delay = next_at - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        t = threading.Thread(target=fire, args=(i,))
        t.start()
        threads.append(t)
    for t in threads:
        t.join(timeout=60)
    lat_ms = np.array(lats) * 1e3
    return dict(
        qps_offered=round(qps, 1),
        completed=len(lats),
        errors=len(errors),
        error_kinds=sorted(set(errors)),
        p50_ms=round(float(np.percentile(lat_ms, 50)), 2),
        p99_ms=round(float(np.percentile(lat_ms, 99)), 2),
        p999_ms=round(float(np.percentile(lat_ms, 99.9)), 2),
        retries=retries[0],
        hedges=hedges[0],
        degraded=degraded_n[0],
    )


def _await_recovery(router, test, *, timeout_s: float = 30.0):
    """Keep light traffic flowing until the ejected replica is readmitted
    (probes advance the wedged replica's dispatch window past its end)."""
    t0 = time.time()
    i = 0
    while time.time() - t0 < timeout_s:
        if router.event_counts().get("readmit", 0) > 0:
            return True
        try:
            router.search(test[i % len(test)])
        except Exception:  # noqa: BLE001 — recovery traffic is best-effort
            pass
        i += 1
        time.sleep(0.05)
    return router.event_counts().get("readmit", 0) > 0


def _series_total(snap: dict, name: str) -> float:
    """Sum a metric's value (counters/gauges) or observation count
    (histograms) across every label set in the snapshot."""
    entry = snap.get(name)
    if entry is None:
        return 0.0
    if entry["kind"] == "histogram":
        return float(sum(row["hist"]["count"] for row in entry["series"]))
    return float(sum(row["value"] for row in entry["series"]))


def _closed_loop_seq(router, test, *, n: int, seed: int) -> float:
    """Sequential closed-loop throughput (one caller pinned) — the
    low-variance probe the overhead guard compares on/off with."""
    rng = np.random.default_rng(seed)
    order = rng.integers(0, len(test), n)
    t0 = time.perf_counter()
    for i in order:
        router.search(test[i])
    return n / (time.perf_counter() - t0)


def telemetry(smoke: bool = False, seed: int = 0):
    """Telemetry scenario (DESIGN.md §3.11): a store-backed two_stage tier
    behind the router with deterministic 1-in-4 request tracing. Records
    the full ``obs.snapshot()``, a p99 exemplar trace, and the measured
    instrumentation overhead (same tier, registry disabled vs enabled,
    best-of-k alternating trials) into the bench payload. The smoke
    assertions here are the CI contract: non-zero engine/router/store
    series, a valid exemplar span tree, bounded overhead.
    """
    # Reset BEFORE building the tier: engines/routers pre-bind their series
    # handles at construction, and a reset would orphan existing handles.
    obs.reset()
    if smoke:
        n, gl, n_queries, n_probe, trials = 1500, 64, 160, 96, 3
    else:
        n, gl, n_queries, n_probe, trials = 6000, 256, 400, 200, 3
    data = make_dataset("dense_embed", n=n + 64, seed=seed)
    train, test = data[:n], data[n:]
    idx = PDASCIndex.build(train, gl=gl, distance="euclidean",
                           radius_quantile=0.35, store="int8",
                           store_block=128)
    idx.release_dense_payload()  # serve from the tiered store, not the seed
    query = Query(k=10, execution="two_stage", beam=32, rerank_width=64,
                  with_stats=False)
    rs = ReplicaSet(idx, query, n_replicas=2, batch_size=8, max_wait_ms=1.0)
    router = Router(rs, RouterConfig(deadline_s=30.0, seed=seed,
                                     trace_every=4))
    try:
        warm = [r.submit(test[0]) for r in rs.replicas]
        for req in warm:
            req.wait(timeout=300)

        # Overhead guard: alternate disabled/enabled trials over the same
        # tier and compare best-of throughput. Tracing is suspended for
        # both legs (its per-sampled-request block_until_ready is a
        # *measurement* cost the guard is not about); the enabled leg pays
        # every counter/gauge/histogram update on the full request path.
        every_n, router._sampler.every_n = router._sampler.every_n, 0
        qps_off, qps_on = [], []
        for t in range(trials):
            obs.set_enabled(False)
            qps_off.append(_closed_loop_seq(router, test, n=n_probe,
                                            seed=seed + 10 + t))
            obs.set_enabled(True)
            qps_on.append(_closed_loop_seq(router, test, n=n_probe,
                                           seed=seed + 10 + t))
        router._sampler.every_n = every_n
        overhead = dict(
            qps_uninstrumented=round(max(qps_off), 1),
            qps_instrumented=round(max(qps_on), 1),
            ratio=round(max(qps_on) / max(qps_off), 3),
            trials=trials, probe_queries=n_probe,
        )

        # Traced traffic: every 4th request records the full span tree
        # (queue -> dispatch -> batch -> scan -> rerank -> granule fetch).
        rng = np.random.default_rng(seed + 1)
        lats = []
        for i in rng.integers(0, len(test), n_queries):
            res = router.search(test[i])
            lats.append(res.latency_s)
        p99_s = float(np.percentile(np.array(lats), 99))
        exemplar = router.traces.exemplar(p99_s)

        snap = obs.snapshot()
        subsystems = sorted({mnames.subsystem(k) for k in snap})
        n_series = sum(len(v["series"]) for v in snap.values())
        row = dict(
            scenario="telemetry",
            config=dict(dataset="dense_embed", n=n, gl=gl,
                        n_queries=n_queries, store="int8",
                        execution="two_stage", n_replicas=2, trace_every=4),
            p99_ms=round(p99_s * 1e3, 2),
            n_series=n_series,
            subsystems=subsystems,
            overhead=overhead,
            key_series={name: _series_total(snap, name) for name in (
                mnames.ENGINE_REQUESTS, mnames.ENGINE_BATCHES,
                mnames.ROUTER_REQUESTS, mnames.ROUTER_LATENCY,
                mnames.PLAN_EXECUTIONS, mnames.STORE_FETCHES,
                mnames.STORE_FETCH_BYTES, mnames.TRACE_FINISHED,
            )},
            exemplar_trace=(exemplar.to_dict() if exemplar else None),
        )
        print(f"[serve] telemetry: {n_series} series across "
              f"{subsystems} p99={row['p99_ms']}ms "
              f"overhead_ratio={overhead['ratio']}", flush=True)

        # -- the CI contract (smoke and full) ------------------------------
        for name in (mnames.ENGINE_REQUESTS, mnames.ROUTER_REQUESTS,
                     mnames.STORE_FETCHES, mnames.PLAN_EXECUTIONS):
            assert _series_total(snap, name) > 0, (
                f"telemetry: series {name} is zero/absent after "
                f"{n_queries} two_stage queries"
            )
        assert n_series >= 25 and len(subsystems) >= 5, (
            f"telemetry: expected >= 25 series over >= 5 subsystems, got "
            f"{n_series} over {subsystems}"
        )
        assert exemplar is not None, "telemetry: no trace was retained"
        span_names = {s.name for s in exemplar.root.walk()}
        for expect in ("attempt", "queue_wait", "execute", "plan", "scan",
                       "rerank", "granule_fetch"):
            assert expect in span_names, (
                f"telemetry: exemplar trace is missing a {expect!r} span "
                f"(got {sorted(span_names)})"
            )
        assert overhead["ratio"] >= 0.95, (
            f"telemetry: instrumented throughput is "
            f"{overhead['ratio']:.3f}x uninstrumented (< 0.95x bound): "
            f"{overhead}"
        )
        return row
    finally:
        router.close(close_replicas=True)


def quality(smoke: bool = False, seed: int = 0):
    """Quality & SLO scenario (DESIGN.md §3.12): a store-backed two_stage
    tier with shadow recall sampling and an SLO tracker with multi-rate
    burn alerts. The three acceptance bars (smoke and full):

      * the online (shadow-sampled) recall estimate lands within +-0.05 of
        the offline recall computed over the same served queries,
      * the SLO tracker fires >= 1 burn alert under an injected wedge and
        ZERO on the fault-free leg,
      * instrumented + shadow-sampled throughput stays >= 0.93x the
        uninstrumented tier (same alternating best-of guard as telemetry).
    """
    obs.reset()  # before building: engines pre-bind series handles
    if smoke:
        n, gl, n_queries, n_probe, trials = 1500, 64, 240, 96, 3
        n_slo, n_wedged = 60, 36
    else:
        n, gl, n_queries, n_probe, trials = 6000, 256, 480, 200, 3
        n_slo, n_wedged = 120, 48
    k = 10
    data = make_dataset("dense_embed", n=n + 64, seed=seed)
    train, test = data[:n], data[n:]
    idx = PDASCIndex.build(train, gl=gl, distance="euclidean",
                           radius_quantile=0.35, store="int8",
                           store_block=128)
    idx.release_dense_payload()
    query = Query(k=k, execution="two_stage", beam=32, rerank_width=64,
                  with_stats=False)
    rs = ReplicaSet(idx, query, n_replicas=2, batch_size=8, max_wait_ms=1.0)
    router = Router(rs, RouterConfig(deadline_s=30.0, seed=seed,
                                     trace_every=4, shadow_every=4))
    est = router.quality
    try:
        warm = [r.submit(test[0]) for r in rs.replicas]
        for req in warm:
            req.wait(timeout=300)
        # Warm the shadow path too (reference read + exact-kNN compile on
        # the worker) so the overhead guard never times a compile.
        for i in range(4):
            router.search(test[i])
        assert est.drain(timeout=120), "quality: shadow warmup never drained"

        # -- (c) overhead guard: uninstrumented vs instrumented+shadowed --
        every_n, router._sampler.every_n = router._sampler.every_n, 0
        qps_off, qps_on = [], []
        for t in range(trials):
            obs.set_enabled(False)
            est.every_n = 0
            qps_off.append(_closed_loop_seq(router, test, n=n_probe,
                                            seed=seed + 10 + t))
            obs.set_enabled(True)
            est.every_n = 4
            qps_on.append(_closed_loop_seq(router, test, n=n_probe,
                                           seed=seed + 10 + t))
        router._sampler.every_n = every_n
        est.drain(timeout=120)
        overhead = dict(
            qps_uninstrumented=round(max(qps_off), 1),
            qps_instrumented=round(max(qps_on), 1),
            ratio=round(max(qps_on) / max(qps_off), 3),
            trials=trials, probe_queries=n_probe, shadow_every=4,
        )

        # -- (a) measured pass: online estimate vs offline ground truth ---
        est.reset_stats()
        rng = np.random.default_rng(seed + 1)
        rows_served = []  # (test row, served ids) for EVERY query
        lats = []
        for i in rng.integers(0, len(test), n_queries):
            res = router.search(test[int(i)])
            rows_served.append((int(i), np.asarray(res.ids).reshape(-1)))
            lats.append(res.latency_s)
        assert est.drain(timeout=120), "quality: shadow queue never drained"
        online = est.estimate()
        from repro.baselines.exact import exact_knn

        q_rows = np.array([r for r, _ in rows_served])
        _, gt = exact_knn(test[q_rows], train, distance="euclidean", k=k)
        gt = np.asarray(gt)
        offline = float(np.mean([
            len(set(int(x) for x in served if x >= 0)
                & set(int(x) for x in gt[j])) / k
            for j, (_, served) in enumerate(rows_served)
        ]))

        # -- (b) SLO: zero alerts fault-free, >= 1 under a wedge ----------
        p99_s = float(np.percentile(np.array(lats), 99))
        target_s = max(5.0 * p99_s, 0.25)
        spec = obs.SLOSpec(latency_p99_s=target_s, window_s=8.0,
                           fast_window_frac=0.25, min_samples=4)
        slo_ff = obs.SLOTracker(spec)
        router.slo = slo_ff  # hooks pick the tracker up per request
        for i in rng.integers(0, len(test), n_slo):
            router.search(test[int(i)])
            slo_ff.evaluate()
    finally:
        router.close(close_replicas=True)

    # Wedged leg: 1 of 2 replicas stalls 0.8s per dispatch mid-window —
    # far past the derived latency target. Hedging is off so the stalls
    # stay caller-visible as latency (not rescued), which is exactly what
    # the burn alert must catch.
    wedge_plan = f"wedge:r1@6+{n_wedged // 3}:0.8"
    rs2 = ReplicaSet(idx, query, n_replicas=2, batch_size=8,
                     max_wait_ms=1.0, degraded_query=degraded(query),
                     fault_plan=FaultPlan.parse(wedge_plan))
    slo_wedged = obs.SLOTracker(spec)
    router2 = Router(rs2, RouterConfig(deadline_s=30.0, hedge=False,
                                       seed=seed),
                     slo=slo_wedged)
    try:
        warm = [r.submit(test[0]) for r in rs2.replicas]
        for req in warm:
            req.wait(timeout=300)
        rng2 = np.random.default_rng(seed + 2)
        for i in rng2.integers(0, len(test), n_wedged):
            router2.search(test[int(i)])
            slo_wedged.evaluate()
    finally:
        router2.close(close_replicas=True)

    row = dict(
        scenario="quality",
        config=dict(dataset="dense_embed", n=n, gl=gl,
                    n_queries=n_queries, store="int8",
                    execution="two_stage", n_replicas=2,
                    trace_every=4, shadow_every=4, k=k),
        online_recall=round(online["recall"], 4),
        online_wilson=[round(online["wilson_lo"], 4),
                       round(online["wilson_hi"], 4)],
        shadow_samples=online["queries"],
        offline_recall=round(offline, 4),
        recall_gap=round(abs(online["recall"] - offline), 4),
        slo=dict(latency_target_ms=round(target_s * 1e3, 1),
                 fault_free_alerts=sum(slo_ff.alert_counts().values()),
                 wedged_alerts=sum(slo_wedged.alert_counts().values()),
                 wedged_events=slo_wedged.events()[:8],
                 faults=wedge_plan),
        overhead=overhead,
    )
    print(f"[serve] quality: online={row['online_recall']} "
          f"offline={row['offline_recall']} gap={row['recall_gap']} "
          f"({row['shadow_samples']} shadow samples) "
          f"slo_alerts=ff:{row['slo']['fault_free_alerts']}/"
          f"wedged:{row['slo']['wedged_alerts']} "
          f"overhead_ratio={overhead['ratio']}", flush=True)

    # -- the CI contract (smoke and full) ---------------------------------
    assert online["recall"] is not None and online["queries"] >= 30, (
        f"quality: too few shadow samples answered: {online}"
    )
    assert abs(online["recall"] - offline) <= 0.05, (
        f"quality: online estimate {online['recall']:.3f} vs offline "
        f"{offline:.3f} over the same served queries (gap > 0.05)"
    )
    assert sum(slo_ff.alert_counts().values()) == 0, (
        f"quality: SLO burn alert fired on the fault-free leg: "
        f"{slo_ff.events()}"
    )
    assert sum(slo_wedged.alert_counts().values()) >= 1, (
        f"quality: no SLO burn alert under {wedge_plan}: "
        f"{slo_wedged.status()}"
    )
    assert overhead["ratio"] >= 0.93, (
        f"quality: instrumented+shadowed throughput is "
        f"{overhead['ratio']:.3f}x uninstrumented (< 0.93x bound): "
        f"{overhead}"
    )
    return row


def run(smoke: bool = False, seed: int = 0):
    idx, test, cfg = _build(smoke, seed)
    query = Query(k=10, execution="beam", beam=32, with_stats=False)
    n_open = 200 if smoke else 600
    # The wedge window is in per-replica handler dispatches: it opens a few
    # batches in (mid-run for any sane traffic level) and is short enough
    # that post-ejection probes can cross it to the recovery side.
    wedge = FaultPlan.parse("wedge:r1@6+5:0.5")

    rows = []
    scenarios = [("fault_free", None), ("wedged", wedge)]
    for name, plan in scenarios:
        rs, router = _make_tier(idx, query, plan, seed)
        try:
            if smoke:
                sat_qps, sat_errors = None, 0
                qps = 120.0
            else:
                sat_qps, sat_errors = _closed_loop_qps(router, test)
                qps = 0.6 * sat_qps
            row = _open_loop(router, test, qps=qps, n=n_open, seed=seed + 1)
            recovered = None
            if plan is not None:
                recovered = _await_recovery(router, test)
            events = router.event_counts()
            row.update(
                scenario=name, config=cfg, n_replicas=N_REPLICAS,
                faults=("wedge:r1@6+5:0.5" if plan is not None else None),
                saturation_qps=(round(sat_qps, 1) if sat_qps else None),
                saturation_errors=sat_errors,
                events=events,
            )
            rows.append(row)
            print(f"[serve] {name}: offered={row['qps_offered']}qps "
                  f"p50={row['p50_ms']}ms p99={row['p99_ms']}ms "
                  f"p999={row['p999_ms']}ms errors={row['errors']} "
                  f"retries={row['retries']} hedges={row['hedges']} "
                  f"events={events}", flush=True)
            assert row["errors"] == 0, (
                f"{name}: {row['errors']} caller-visible errors "
                f"({row['error_kinds']}) — the router must absorb faults"
            )
            assert sat_errors == 0, (
                f"{name}: {sat_errors} errors during the saturation sweep"
            )
            if plan is not None:
                assert events.get("eject", 0) >= 1, (
                    f"wedged replica was never ejected: {events}"
                )
                assert recovered, (
                    f"wedged replica was never readmitted: {events}"
                )
        finally:
            router.close(close_replicas=True)

    if not smoke:
        ratio = rows[1]["p99_ms"] / rows[0]["p99_ms"]
        rows[1]["p99_vs_fault_free"] = round(ratio, 2)
        assert ratio <= 3.0, (
            f"faulted p99 {rows[1]['p99_ms']}ms is {ratio:.1f}x the "
            f"fault-free {rows[0]['p99_ms']}ms (> 3x bound)"
        )
    return rows


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="tiny config, fault-recovery assertions only (CI)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default="experiments/serve.json")
    p.add_argument("--bench-out", default="BENCH_serve.json")
    args = p.parse_args(argv)

    rows = run(smoke=args.smoke, seed=args.seed)
    telemetry_row = telemetry(smoke=args.smoke, seed=args.seed)
    quality_row = quality(smoke=args.smoke, seed=args.seed)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows + [telemetry_row, quality_row], f, indent=1)
    # Always (smoke included) leave a metrics snapshot on disk: CI feeds it
    # to ``python -m repro.obs.report`` as the offline-report contract.
    metrics_out = os.path.join(os.path.dirname(args.out) or ".",
                               "serve_metrics.json")
    obs.MetricsDumper(obs.registry(), metrics_out, period_s=0).dump()
    print(f"[serve] wrote {metrics_out}")
    if not args.smoke:
        payload = dict(
            bench="replicated_serving_under_faults",
            baseline="fault-free replica pool (same router, no FaultPlan)",
            new="1-of-4 replicas wedged mid-run: hedge/retry routing, "
                "health ejection + half-open readmission, zero "
                "caller-visible errors",
            rows=rows,
            telemetry=telemetry_row,
            quality=quality_row,
        )
        with open(args.bench_out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"[serve] wrote {args.bench_out}")


if __name__ == "__main__":
    main()
