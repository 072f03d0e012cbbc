"""Replicated fault-tolerant serving tier (DESIGN.md §3.10): fault-plan
determinism, router parity/retry/hedge/health behaviour, write fan-out and
crash-replay convergence, admission control with graceful degradation."""

import threading
import time

import numpy as np
import pytest

from repro.core.index import PDASCIndex
from repro.query import Query, degraded
from repro.serving import (
    BatchingEngine,
    FaultPlan,
    FaultSpec,
    InjectedFault,
    Overloaded,
    QueryHandler,
    ReplicaDown,
    ReplicaSet,
    Router,
    RouterConfig,
    clone_index,
)
from repro.serving.faults import ReplicaCrashed


@pytest.fixture(scope="module")
def built():
    rng = np.random.default_rng(7)
    X = rng.normal(size=(600, 12)).astype(np.float32)
    idx = PDASCIndex.build(X, gl=64, distance="euclidean")
    return idx, X


QUERY = Query(k=5, execution="beam", beam=16, with_stats=False)


def _tier(idx, *, n_replicas=2, fault_plan=None, cfg=None, **kw):
    rs = ReplicaSet(idx, QUERY, n_replicas=n_replicas, batch_size=4,
                    max_wait_ms=0.5, degraded_query=degraded(QUERY),
                    fault_plan=fault_plan, **kw)
    router = Router(rs, cfg or RouterConfig(
        deadline_s=10.0, eject_failures=2, probe_cooldown_s=0.05,
        probe_interval_s=0.02, seed=0))
    return rs, router


# --------------------------- fault plan --------------------------------------


def test_fault_plan_parse_roundtrip():
    plan = FaultPlan.parse("wedge:r1@20+8; error:r0@5+3 , latency:r2@0+4:0.1")
    kinds = sorted((s.kind, s.replica, s.start, s.duration)
                   for s in plan.specs)
    assert kinds == [("error", 0, 5, 3), ("latency", 2, 0, 4),
                     ("wedge", 1, 20, 8)]
    lat = next(s for s in plan.specs if s.kind == "latency")
    assert lat.delay_s == pytest.approx(0.1)
    with pytest.raises(ValueError):
        FaultPlan.parse("explode:r0@1+1")
    with pytest.raises(ValueError):
        FaultPlan.parse("error:r0@1")


def test_fault_injection_is_dispatch_deterministic():
    """Same plan, same dispatch sequence -> identical fault decisions —
    twice over, with no wall clock involved for error faults."""
    plan = FaultPlan((FaultSpec("error", 0, 3, 2),))

    def run():
        inj = plan.injector(0)
        outcomes = []
        for _ in range(8):
            try:
                inj.on_dispatch()
                outcomes.append("ok")
            except InjectedFault:
                outcomes.append("err")
        return outcomes

    first, second = run(), run()
    assert first == second == ["ok"] * 3 + ["err"] * 2 + ["ok"] * 3


def test_fault_plan_generate_seeded():
    a = FaultPlan.generate(seed=3, n_replicas=4)
    b = FaultPlan.generate(seed=3, n_replicas=4)
    assert a.specs == b.specs
    assert all(s.replica < 4 for s in a.specs)
    assert a.specs != FaultPlan.generate(seed=4, n_replicas=4).specs


# --------------------------- replica set -------------------------------------


def test_clone_index_shares_immutables_rejects_dirty(built):
    idx, X = built
    clone = clone_index(idx)
    assert clone.data is idx.data  # build artifacts shared by reference
    assert clone.delta is None and clone.tombstones is None
    dirty = clone_index(idx)
    dirty.enable_mutations(delta_capacity=64)
    dirty.upsert(X[:1] + 50.0)
    with pytest.raises(ValueError, match="clean online tiers"):
        clone_index(dirty)


def test_router_results_match_direct_plan(built):
    idx, X = built
    rs, router = _tier(idx)
    try:
        ref = idx.plan(QUERY)(X[:8])
        for i in range(8):
            res = router.search(X[i])
            np.testing.assert_array_equal(res.ids, np.asarray(ref.ids)[i])
            np.testing.assert_allclose(res.dists, np.asarray(ref.dists)[i],
                                       rtol=1e-5)
            assert not res.degraded
    finally:
        router.close(close_replicas=True)


def test_write_fanout_converges_and_ids_agree(built):
    idx, X = built
    rs, router = _tier(idx)
    try:
        ids = rs.upsert(X[:3] + 100.0)
        assert len(ids) == 3
        assert rs.delete(np.asarray([ids[1]])) == 1
        # both replicas serve the upserted points (minus the deleted one)
        for probe, want in ((X[0] + 100.0, ids[0]), (X[2] + 100.0, ids[2])):
            seen = set()
            for _ in range(12):
                res = router.search(probe)
                assert res.ids[0] == want
                assert ids[1] not in set(res.ids.tolist())
                seen.add(res.replica)
            assert seen == {0, 1}  # P2C really spread across the fleet
    finally:
        router.close(close_replicas=True)


def test_kill_restart_replays_log_suffix(built):
    idx, X = built
    rs, router = _tier(idx)
    try:
        first = rs.upsert(X[:2] + 100.0)
        rs.kill(1)
        assert not rs.replicas[1].alive
        with pytest.raises(ReplicaDown):
            rs.replicas[1].submit(X[0])
        # writes continue against the survivor; replica 1 misses them
        second = rs.upsert(X[2:4] + 200.0)
        assert rs.replicas[1].applied_seq < rs.log.last_seq
        rs.restart(1)
        assert rs.replicas[1].applied_seq == rs.log.last_seq
        # the restarted replica assigned the SAME ids by ordered replay
        req = rs.replicas[1].submit(X[3] + 200.0)
        dists, ids = req.wait(timeout=30)
        assert ids[0] == second[1]
        req0 = rs.replicas[0].submit(X[3] + 200.0)
        _, ids0 = req0.wait(timeout=30)
        assert ids0[0] == ids[0]
        assert first[0] != second[0]
    finally:
        router.close(close_replicas=True)


def test_write_with_all_replicas_down_raises_and_replays(built):
    idx, X = built
    rs, router = _tier(idx)
    try:
        rs.kill(0)
        rs.kill(1)
        with pytest.raises(ReplicaDown):
            rs.upsert(X[:1] + 300.0)
        # the op stays in the log: a restart replays it
        rs.restart(0)
        res = rs.replicas[0].submit(X[0] + 300.0).wait(timeout=30)
        # first id past the build's points (leaf_ids is slot-padded)
        next_id = int((np.asarray(idx.data.leaf_ids) >= 0).sum())
        assert res[1][0] == next_id
    finally:
        router.close(close_replicas=True)


# --------------------------- router fault handling ---------------------------


def test_retry_rescues_error_burst(built):
    idx, X = built
    plan = FaultPlan.parse("error:r0@1+50")  # r0 errors on every dispatch
    rs, router = _tier(idx, fault_plan=plan, cfg=RouterConfig(
        deadline_s=10.0, max_retries=2, hedge=False, eject_failures=2,
        probe_cooldown_s=10.0, probe_interval_s=0.5, seed=0))
    try:
        ok = 0
        for i in range(20):
            res = router.search(X[i])
            ok += 1
            assert res.replica in (0, 1)
        assert ok == 20  # zero caller-visible errors
        ev = router.event_counts()
        assert ev.get("eject", 0) >= 1  # r0 ejected after consec failures
        assert router.stats["retries"] >= 1
    finally:
        router.close(close_replicas=True)


def test_hedge_rescues_wedged_replica_and_health_readmits(built):
    idx, X = built
    # r1 wedges (0.4s stall per dispatch) for a short window
    plan = FaultPlan.parse("wedge:r1@1+4:0.4")
    rs, router = _tier(idx, fault_plan=plan, cfg=RouterConfig(
        deadline_s=10.0, hedge=True, hedge_min_s=0.02, eject_failures=2,
        probe_cooldown_s=0.05, probe_timeout_s=0.2, probe_interval_s=0.02,
        seed=0))
    try:
        for i in range(30):
            res = router.search(X[i % len(X)])
            assert res.ids.shape == (QUERY.k,)
            time.sleep(0.005)
        deadline = time.time() + 30
        while time.time() < deadline:
            ev = router.event_counts()
            if ev.get("readmit", 0) >= 1:
                break
            router.search(X[0])
            time.sleep(0.05)
        ev = router.event_counts()
        assert ev.get("hedge", 0) >= 1, ev
        assert ev.get("eject", 0) >= 1, ev
        assert ev.get("half_open", 0) >= 1, ev
        assert ev.get("readmit", 0) >= 1, ev
        assert router.stats["successes"] >= 30
    finally:
        router.close(close_replicas=True)


def test_crash_fault_triggers_restart_and_recovery(built):
    idx, X = built
    plan = FaultPlan.parse("crash:r0@2+1")
    rs, router = _tier(idx, fault_plan=plan, cfg=RouterConfig(
        deadline_s=10.0, hedge=False, max_retries=2, eject_failures=1,
        probe_cooldown_s=0.05, probe_timeout_s=1.0, probe_interval_s=0.02,
        seed=0))
    try:
        errs = 0
        for i in range(25):
            try:
                router.search(X[i % len(X)])
            except Exception:  # noqa: BLE001 — the count IS the assertion
                errs += 1
            time.sleep(0.01)
        assert errs == 0
        deadline = time.time() + 30
        while time.time() < deadline and not rs.replicas[0].alive:
            time.sleep(0.05)
        ev = router.event_counts()
        assert ev.get("crash", 0) >= 1, ev
        assert ev.get("restart", 0) >= 1, ev
        assert rs.replicas[0].alive
    finally:
        router.close(close_replicas=True)


def test_deadline_exceeded_when_all_replicas_wedge(built):
    idx, X = built
    plan = FaultPlan.parse("wedge:r0@0+200:0.3;wedge:r1@0+200:0.3")
    rs, router = _tier(idx, fault_plan=plan, cfg=RouterConfig(
        deadline_s=0.15, max_retries=1, hedge=False, eject_failures=50,
        probe_cooldown_s=30.0, probe_interval_s=1.0, seed=0))
    try:
        from repro.serving import DeadlineExceeded

        with pytest.raises(DeadlineExceeded):
            router.search(X[0])
        assert router.stats["deadline_exceeded"] == 1
    finally:
        router.close(close_replicas=True)


# --------------------------- admission + degradation -------------------------


def test_admission_rejects_past_queue_limit(built):
    idx, X = built
    rs, router = _tier(idx, cfg=RouterConfig(
        deadline_s=10.0, queue_limit=4, degrade_at=2.0,  # degrade disabled
        hedge=False, seed=0))
    try:
        with router._lock:
            router._inflight = 4  # saturate the budget directly
        with pytest.raises(Overloaded):
            router.submit(X[0])
        assert router.stats["rejected"] == 1
        with router._lock:
            router._inflight = 0
        assert router.search(X[0]).ids.shape == (QUERY.k,)
    finally:
        router.close(close_replicas=True)


def test_degradation_ladder_serves_cheaper_plan(built):
    idx, X = built
    rs, router = _tier(idx, cfg=RouterConfig(
        deadline_s=10.0, queue_limit=8, degrade_at=0.5, hedge=False, seed=0))
    try:
        with router._lock:
            router._inflight = 4  # past the watermark, under the limit
        res = router.submit(X[0]).wait(timeout=30)
        assert res.degraded
        assert res.ids.shape == (QUERY.k,)
        # degraded results still come from the narrower-beam plan: top-1
        # agrees with the exact plan on this easy query
        ref = idx.plan(QUERY)(X[0])
        assert res.ids[0] == int(np.asarray(ref.ids)[0])
        with router._lock:
            router._inflight -= 4
    finally:
        router.close(close_replicas=True)


def test_degraded_scan_only_plan_skips_exact_rerank(built):
    idx, X = built
    base = PDASCIndex.build(X, gl=64, distance="euclidean", store="int8",
                            store_block=64)
    base.release_dense_payload()
    q = Query(k=5, execution="two_stage", rerank_width=32, with_stats=False)
    dq = degraded(q)
    assert not dq.exact_rerank and dq.rerank_width == q.k
    plan = base.plan(dq)
    assert "scan-only" in plan.explain()
    exact = base.plan(q)(X[0])  # exact pipeline fetches payload rows
    fetches_before = base.store.exact.stats["fetches"]
    res = plan(X[:4])
    assert np.asarray(res.ids).shape == (4, 5)
    # scan-only ranking still lands on the true neighbour for the trivial
    # self-query (quantisation error is tiny relative to the margin)
    res1 = plan(X[0])
    assert int(np.asarray(res1.ids)[0]) == int(np.asarray(exact.ids)[0])
    # ... and never touched the exact payload tier (zero fetch traffic)
    assert base.store.exact.stats["fetches"] == fetches_before


# --------------------------- stress ------------------------------------------


@pytest.mark.stress
def test_long_faulted_schedule_zero_caller_errors(built):
    """Soak: a generated multi-fault schedule over 4 replicas with mixed
    search + write traffic — zero caller-visible search errors, and every
    ejection is eventually followed by recovery events."""
    idx, X = built
    plan = FaultPlan.generate(seed=11, n_replicas=4, n_faults=6,
                              horizon=60, max_duration=5, delay_s=0.2)
    rs = ReplicaSet(idx, QUERY, n_replicas=4, batch_size=4, max_wait_ms=0.5,
                    degraded_query=degraded(QUERY), fault_plan=plan)
    router = Router(rs, RouterConfig(
        deadline_s=15.0, max_retries=3, hedge=True, hedge_min_s=0.02,
        eject_failures=2, probe_cooldown_s=0.05, probe_timeout_s=0.3,
        probe_interval_s=0.02, seed=1))
    rng = np.random.default_rng(0)
    errors = []
    lock = threading.Lock()

    def searcher(w):
        for i in range(60):
            try:
                res = router.search(X[(w * 60 + i) % len(X)])
                assert res.ids.shape == (QUERY.k,)
            except Exception as e:  # noqa: BLE001 — collected for assert
                with lock:
                    errors.append(repr(e))
            time.sleep(0.002)

    try:
        threads = [threading.Thread(target=searcher, args=(w,))
                   for w in range(4)]
        for t in threads:
            t.start()
        for j in range(10):  # interleave writes with the faulted traffic
            rs.upsert(X[rng.integers(len(X))][None] + 100.0 + j)
            time.sleep(0.05)
        for t in threads:
            t.join()
        assert not errors, errors[:5]
        # every replica that went down must be back up (prober restarts)
        # AND readmitted by the router: `alive` flips the moment a wedge
        # fault's dispatch window clears, but an ejected replica only sees
        # probe traffic (on doubling cooldowns), so routing-level recovery
        # lands strictly later — keep traffic flowing until the prober has
        # walked every replica back to healthy.
        deadline = time.time() + 60
        while time.time() < deadline and not (
                all(r.alive for r in rs.replicas)
                and all(s == "healthy"
                        for s in router.health_states().values())):
            router.search(X[0])
            time.sleep(0.05)
        assert all(r.alive for r in rs.replicas)
        assert all(s == "healthy" for s in router.health_states().values())
        # and the fleet converged: replay left every replica at the log head
        assert all(r.applied_seq == rs.log.last_seq for r in rs.replicas)
        # The event log (DESIGN.md §3.11) must show the exact health
        # lifecycle per replica: transitions chain state-to-state (each
        # edge's "from" is the previous edge's "to", starting healthy), and
        # every ejection recovers through eject -> half_open -> readmit.
        transitions = [e for e in router.events() if "from" in e]
        assert transitions, "faulted soak produced no health transitions"
        ejected_rids = {e["replica"] for e in transitions
                        if e["event"] == "eject"}
        assert ejected_rids, "no replica was ever ejected under faults"
        for rid in {e["replica"] for e in transitions}:
            chain = [e for e in transitions if e["replica"] == rid]
            state = "healthy"
            for e in chain:
                assert e["from"] == state, (
                    f"r{rid}: transition {e} does not chain from {state}"
                )
                state = e["to"]
            events = [e["event"] for e in chain]
            for ej in (i for i, ev in enumerate(events) if ev == "eject"):
                rest = events[ej + 1:]
                assert "half_open" in rest and \
                    "readmit" in rest[rest.index("half_open"):], (
                        f"r{rid}: ejection at step {ej} never recovered "
                        f"via half_open -> readmit: {events}"
                    )
            # the soak's convergence loop means nobody ends ejected
            assert state == "healthy", f"r{rid} finished in state {state}"
        # the per-edge transition counters agree with the event log
        counted = sum(v for k, v in router.stats.items()
                      if k.startswith("transition_"))
        assert counted == len(transitions)
    finally:
        router.close(close_replicas=True)


def test_serve_cli_exits_nonzero_on_caller_errors(monkeypatch, tmp_path):
    """The --replicas CLI path fails the run when any caller saw an error:
    every replica erroring leaves nothing to route around."""
    from repro.launch import serve

    # a cache directory given by the environment: main() then changes no
    # process-wide JAX setting for the tests that follow
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    with pytest.raises(SystemExit) as exc:
        serve.main([
            "--n", "600", "--gl", "32", "--queries", "4", "--batch", "4",
            "--mode", "beam", "--beam", "8", "--replicas", "2",
            "--deadline-ms", "20000",
            "--faults", "error:r0@1+1000;error:r1@1+1000",
        ])
    assert "caller-visible errors" in str(exc.value.code)
