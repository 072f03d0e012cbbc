"""One run of one cell: load, build, warm, drive the open loop, check.

The window drives the normal served path: ``BatchingEngine.submit`` over
``QueryHandler(index, Query(...))``, set up as the serving CLI's
single-engine path sets it up (store and released dense payload for
``two_stage``, zero pad rows), from the cell's own config and traffic
files. Everything the window answered is then checked against the plain
reference (``check.py``), after the program's state is freed.
"""

from __future__ import annotations

import gc
import resource
import shutil
import sys
import tempfile
import threading
import time

import jax
import numpy as np

from annbench import check, loadgen, reference, spec, xtrace

ENGINE = "bench"
TRACE_SLICE_S = 10.0  # the traced run profiles this much of its window


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 64 bits or less."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def make_data(cfg: dict, seed: int):
    """(database [n, d], query pool [pool, d]) as host float32, made on the
    device from the seed by the config's recipe."""
    n, d, pool = cfg["n"], cfg["d"], cfg["pool"]
    gen = spec.recipe(cfg["recipe"]).generate
    x = gen(seed_key(seed), n_rows=n + pool, d=d,
            **cfg.get("recipe_params", {}))
    host = np.asarray(x)
    del x
    return host[:n], host[n:]


def build(cfg: dict, traffic: dict, train: np.ndarray):
    """The index as the serving CLI builds it for the traffic's execution."""
    from repro.core.index import PDASCIndex

    two_stage = traffic["execution"] == "two_stage"
    store = dict(store=cfg["store"], store_block=cfg["store_block"]) \
        if two_stage else {}
    t0 = time.perf_counter()
    idx = PDASCIndex.build(
        train, gl=cfg["gl"], distance=cfg["distance"],
        radius_quantile=traffic["radius_quantile"], **cfg["build"], **store)
    jax.block_until_ready(idx.data)
    build_s = time.perf_counter() - t0
    if two_stage:
        idx.release_dense_payload()
    return idx, build_s


def plan_shapes(idx, cfg: dict, traffic: dict) -> dict:
    return dict(
        batch=traffic["batch"], d=cfg["d"], k=cfg["k"],
        beam=traffic["beam"], execution=traffic["execution"],
        rerank_width=traffic.get("rerank_width", 128),
        level_sizes=[int(lv.valid.shape[0]) for lv in idx.data.levels],
        max_children=list(idx.max_children),
    )


def engine_for(idx, cfg: dict, traffic: dict, fault=None):
    """``BatchingEngine`` over ``QueryHandler``; ``fault`` (tests only)
    wraps the handler to break the timed path underneath the engine."""
    from repro.kernels.ops import KernelConfig
    from repro.query import Query
    from repro.serving import BatchingEngine, QueryHandler

    query = Query(k=cfg["k"], execution=traffic["execution"],
                  beam=traffic["beam"],
                  rerank_width=traffic.get("rerank_width", 128),
                  kernel=KernelConfig(auto=False))
    handler = QueryHandler(idx, query)
    if fault is not None:
        handler = fault(handler)

    def annotated(batch, n_valid):
        with jax.profiler.TraceAnnotation(xtrace.HANDLER_SPAN):
            return handler(batch, n_valid)

    return BatchingEngine(
        annotated, batch_size=traffic["batch"],
        max_wait_ms=traffic["max_wait_ms"],
        pad_payload=np.zeros(cfg["d"], np.float32), name=ENGINE)


def warm(engine, pool: np.ndarray, batch: int, rounds: int = 3) -> None:
    """Run full batches through the engine: the one compiled batch shape."""
    for r in range(rounds):
        reqs = [engine.submit(pool[(r * batch + i) % len(pool)])
                for i in range(batch)]
        for q in reqs:
            q.wait(timeout=1200)


class _Compiles:
    """Counts traces and backend compiles while ``on`` (the window)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.on = False
        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            with self._lock:
                self.count += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._event)


def _engine_hists():
    from repro import obs
    from repro.obs import names

    out = {}
    for key, name in (("queue_wait", names.ENGINE_QUEUE_WAIT),
                      ("occupancy", names.ENGINE_BATCH_OCCUPANCY),
                      ("handler", names.ENGINE_HANDLER_TIME)):
        h = obs.histogram(name, engine=ENGINE)
        out[key] = (h.sum, h.count)
    return out


class _SliceTracer(threading.Thread):
    """Profiles ``[start + t0, start + t1)`` of the window from a thread of
    its own, marks it with the window span, and snapshots the engine's
    histograms at both ends, so every per-layer metric covers one slice."""

    def __init__(self, log_dir: str, t0: float, t1: float):
        super().__init__(daemon=True)
        self.log_dir, self.t0, self.t1 = log_dir, t0, t1
        self.start_pc = None
        self.bounds = None
        self._go = threading.Event()

    def begin(self, start: float) -> None:
        self.start_pc = start
        self._go.set()

    def _sleep_until(self, t: float) -> None:
        time.sleep(max(t - time.perf_counter(), 0.0))

    def run(self):
        self._go.wait()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self._sleep_until(self.start_pc + self.t0)
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.before = _engine_hists()
        lo = time.perf_counter()
        with jax.profiler.TraceAnnotation(xtrace.WINDOW_SPAN):
            self._sleep_until(self.start_pc + self.t1)
        self.after = _engine_hists()
        self.bounds = (lo, time.perf_counter())
        jax.profiler.stop_trace()


def _span_stages(roots, bounds) -> list:
    """Per traced request submitted inside ``bounds`` (perf_counter): its
    batch size and seconds per stage span."""
    out = []
    for root in roots:
        if not bounds[0] <= root.t0 < bounds[1]:
            continue
        for ex in root.children:
            if ex.name != "execute":
                continue
            stages: dict = {}
            for s in ex.walk():
                if s is not ex:
                    stages[s.name] = stages.get(s.name, 0.0) + s.duration
            out.append(dict(batch=int(ex.attrs.get("batch", 1)),
                            stages=stages))
    return out


def _memory(stat: str) -> int:
    """``stat`` of the device allocator on the fullest chip."""
    vals = [d.memory_stats().get(stat, 0)
            for d in jax.local_devices() if d.memory_stats()]
    return int(max(vals)) if vals else 0


def _cpu_seconds() -> float:
    """CPU seconds this process has used so far, all threads."""
    r = resource.getrusage(resource.RUSAGE_SELF)
    return r.ru_utime + r.ru_stime


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run(cell: dict, *, seed: int, seconds: float, trace: bool,
        t_start: float, fault=None, give_up: float = 60.0) -> dict:
    """One run of ``cell`` (``spec.cell``); returns the result line."""
    from repro import obs

    cfg, traffic = cell["config"], cell["traffic"]
    train, pool = make_data(cfg, seed)
    log(f"data {train.shape} pool {pool.shape} "
        f"({time.perf_counter() - t_start:.2f}s since start)")
    idx, build_s = build(cfg, traffic, train)
    log(f"build_s {build_s:.3f} levels {idx.n_levels} "
        f"max_children {idx.max_children}")
    plan = plan_shapes(idx, cfg, traffic)
    engine = engine_for(idx, cfg, traffic, fault=fault)
    warm(engine, pool, traffic["batch"])
    due, rows = loadgen.schedule(seed, rate=traffic["rate_qps"],
                                 seconds=seconds, pool=len(pool))
    payloads = pool[rows]
    roots = []
    if trace:
        def submit(p, on_done):
            root = obs.Trace("request").root
            roots.append(root)
            return engine.submit(p, on_done=on_done, span=root)
    else:
        def submit(p, on_done):
            return engine.submit(p, on_done=on_done)

    k = cfg["k"]
    ids = np.full((len(due), k), -1, np.int64)
    dists = np.full((len(due), k), np.nan)

    def on_result(i, result):
        dists[i], ids[i] = result

    compiles = _Compiles()
    tracer = None
    if trace:
        t0 = max((seconds - TRACE_SLICE_S) / 2, 0.0)
        tracer = _SliceTracer(tempfile.mkdtemp(prefix="bench-trace-"),
                              t0, min(t0 + TRACE_SLICE_S, seconds))
        tracer.start()
    loadgen.quiesce()
    setup_s = time.perf_counter() - t_start
    compiles.on = True
    pauses = loadgen.GcPauses()
    cpu0 = _cpu_seconds()
    out = loadgen.run(submit, payloads, due, seconds=seconds,
                      on_result=on_result, give_up=give_up,
                      on_start=tracer.begin if tracer else None)
    cpu_s = _cpu_seconds() - cpu0
    gc_note = pauses.close()
    gc.unfreeze()
    compiles.on = False
    if tracer is not None:
        tracer.join()
    compiles.close()
    memory_peak = _memory("peak_bytes_in_use")
    served_bytes = _memory("bytes_in_use")  # the index and what serving holds
    engine.close()
    del engine, idx
    gc.collect()

    lat = out.latencies(give_up)
    answered = out.ok
    log(f"window {seconds}s: {len(due)} due, {int(answered.sum())} answered, "
        f"{out.completed_in_window()} in the window; generator lag p99 "
        f"{1e3 * loadgen.percentile(out.lag, 99):.3f} ms max "
        f"{1e3 * out.lag.max(initial=0):.3f} ms at "
        f"{out.due[int(out.lag.argmax())] if len(due) else 0:.2f}s, "
        f"{int((out.lag > 0.05).sum())} over 50 ms; compiles in window "
        f"{compiles.count}; {gc_note}; host CPU {cpu_s / seconds:.2f} "
        f"cores; device bytes in use {served_bytes}, peak {memory_peak}")

    dist_mod = spec.distance(cfg["distance"])
    t_ref = time.perf_counter()
    db = reference.Database(train, dist_mod)
    numbers = check.compare(db, pool, rows, answered, ids, dists, k)
    log("latency ms p50 %.3f p95 %.3f p99 %.3f max %.3f" % tuple(
        1e3 * loadgen.percentile(lat, q) for q in (50, 95, 99, 100)))
    log(f"reference and check {time.perf_counter() - t_ref:.2f}s; "
        f"recall {numbers['recall']!r} scale {numbers['scale']!r}")
    correct, checks = check.verdict(numbers, traffic["limits"],
                                    traffic.get("floors"))

    dev = jax.devices()[0]
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=jax.device_count(), memory_peak_bytes=memory_peak)
    e2e = dict(
        qps=out.completed_in_window() / seconds,
        p95_ms=1e3 * loadgen.percentile(lat, 95),
        p99_ms=1e3 * loadgen.percentile(lat, 99),
        recall_at_10=numbers["recall"],
        hbm_bytes_per_vector=served_bytes / cfg["n"],
        setup_s=setup_s,
    )
    result = dict(correct=bool(correct), attempted=int(len(due)),
                  failed=int(numbers["unanswered"]))
    if not trace:
        result["metrics"] = {m["name"]: dict(value=e2e[m["name"]],
                                             unit=m["unit"])
                             for m in cell["end_to_end"]}
    else:
        tr = xtrace.load(tracer.log_dir)
        shutil.rmtree(tracer.log_dir, ignore_errors=True)
        win = xtrace.window(tr)
        tr["device"] = xtrace.clip(tr["device"], win)
        busy_s = xtrace.busy_seconds(tr["device"], win)
        window_s = (win[1] - win[0]) / 1e9
        ctx = dict(build_s=build_s, plan=plan, device_kind=dev.device_kind,
                   engine={k_: (tracer.after[k_][0] - tracer.before[k_][0],
                                tracer.after[k_][1] - tracer.before[k_][1])
                           for k_ in tracer.after},
                   spans=_span_stages(roots, tracer.bounds), trace=tr,
                   busy_s=busy_s,
                   window_s=window_s)
        metrics = {}
        for m in cell["per_layer"]:
            v = spec.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = dict(value=float(v), unit=m["unit"])
        result["metrics"] = metrics
        device.update(busy_s=busy_s, window_s=window_s)
        result["breakdown"] = xtrace.breakdown(tr, win)
    result["device"] = device
    result["checks"] = checks
    check.print_checks(checks)
    return result
