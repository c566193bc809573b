"""One run of one cell: set-up, the measured window, the comparison.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json`` names its configuration (``configs/<name>.json``) and
traffic mix (``traffic/<name>.json``); each end-to-end metric is read by
``end_to_end/<name>.py`` and each per-layer metric by
``metrics/<name>.py``. Adding a cell, a mix or a metric adds files and
edits none of these.

The served path is the program's own: a ``CatalogStore`` ingests the lake,
``DiscoveryEngine.from_catalog`` opens it with AOT warmup over the mix's
batch buckets, and the traffic goes through ``RequestScheduler.submit``.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import os
import queue
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
CACHE = os.path.join(BENCH, ".cache")     # model, compile and executables
WORK = os.path.join(BENCH, ".work")       # catalogs and traces of a run
GRACE_S = 60.0          # how long answers due in the window are waited for
WARM_ROUNDS = 2         # warm-up batches per bucket before the window

if SRC not in sys.path:
    sys.path.insert(0, SRC)

from bench import check, reference, tracing  # noqa: E402
from bench import lake as lakegen  # noqa: E402
from bench import model as modelcache  # noqa: E402
from bench import traffic as trafficgen  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def rng_for(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *tags])


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # BENCHMARK.json metric entries for this cell
    per_layer: list


class Layout:
    """``BENCHMARK.json`` at ``root`` and the data files under ``bench``."""

    def __init__(self, root: str = ROOT, bench: str = BENCH):
        self.root, self.bench = root, bench
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def _json(self, kind: str, name: str) -> dict:
        with open(os.path.join(self.bench, kind, f"{name}.json")) as f:
            return json.load(f)

    def cell(self, name: str) -> Cell:
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                           f"{sorted(cells)}")
        w = cells[name]

        def mine(m):
            return "workloads" not in m or name in m["workloads"]

        return Cell(name=name, chips=int(w["chips"]),
                    config=self._json("configs", w["config"]),
                    traffic=self._json("traffic", w["traffic"]),
                    end_to_end=[m for m in self.spec["end_to_end"] if mine(m)],
                    per_layer=[m for m in self.spec["per_layer"] if mine(m)])

    def reader(self, kind: str, name: str):
        path = os.path.join(self.bench, kind, f"{name}.py")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def peaks(self, device_kind: str) -> dict:
        with open(os.path.join(self.bench, "peaks.json")) as f:
            table = json.load(f)
        if device_kind not in table["devices"]:
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           f"peaks.json; have {sorted(table['devices'])}")
        return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Rig:
    cell: Cell
    seed: int
    lake: object
    trees: tuple
    engine: object
    scheduler: object
    catalog_dir: str
    buckets: tuple
    setup_parts: dict


def configure_caches() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment names: only the first run of a cell
    in a checkout compiles."""
    import jax

    cache = os.path.join(CACHE, "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def engine_config(cell: Cell, buckets: tuple):
    from repro.service import EngineConfig, LSHConfig

    cfg, w = cell.config["engine"], cell.config["widths"]
    return EngineConfig(
        k=int(w["k"]), mode=cfg["mode"], profile_dtype=cfg["profile_dtype"],
        max_candidates=int(cfg["max_candidates"]),
        candidate_frac=float(cfg["candidate_frac"]),
        cache_entries=int(cfg["cache_entries"]),
        lsh=LSHConfig(n_bands=int(w["n_bands"]),
                      n_coarse_bands=int(w["n_coarse_bands"])),
        batch_buckets=buckets, warmup="serve",
        executable_cache_dir=os.path.join(CACHE, "executables"))


def request(qid: int):
    from repro.service import DiscoveryRequest

    return DiscoveryRequest(name=f"q{qid}", column_id=int(qid))


def setup(cell: Cell, seed: int, *, work: str = WORK) -> Rig:
    from repro.core import ColumnBatch
    from repro.service import (CatalogStore, DiscoveryEngine,
                               RequestScheduler, SchedulerConfig)

    parts = {}
    t = time.perf_counter()
    mdl, trees, trained = modelcache.load_or_train(
        cell.config["model"], CACHE, SRC, log=log)
    parts["model_s"] = time.perf_counter() - t

    t = time.perf_counter()
    spec = lakegen.LakeSpec.from_config(cell.config["lake"])
    lake = lakegen.generate(spec, seed)
    parts["lake_s"] = time.perf_counter() - t

    t = time.perf_counter()
    os.makedirs(work, exist_ok=True)
    catalog_dir = tempfile.mkdtemp(prefix="catalog-", dir=work)
    store = CatalogStore(catalog_dir,
                         n_perm=int(cell.config["widths"]["n_perm"]))
    n_tables = int(lake.table.max()) + 1
    store.add_batch(ColumnBatch(
        values32=lake.values32, char_len=lake.char_len,
        word_cnt=lake.word_cnt, n_rows=lake.n_rows,
        names=[f"c{i}" for i in range(lake.n_columns)],
        table_ids=lake.table), [f"t{i}" for i in range(n_tables)])
    parts["ingest_s"] = time.perf_counter() - t

    t = time.perf_counter()
    buckets = tuple(int(b) for b in cell.traffic["buckets"])
    engine = DiscoveryEngine.from_catalog(store, mdl,
                                          engine_config(cell, buckets))
    scheduler = RequestScheduler(engine, SchedulerConfig(
        batch_buckets=buckets,
        max_wait_ms=float(cell.traffic.get("max_wait_ms", 2.0))))
    rep = engine.warmup_report
    parts["open_warmup_s"] = time.perf_counter() - t
    log(f"warmup: {rep['n_executables']} executables over buckets "
        f"{rep['buckets']}: {rep['cache_hits']} from cache, "
        f"{rep['cache_misses']} compiled ({rep['compile_ms'] / 1e3:.3f} s)")

    # host paths warm too: each bucket served full, on noise columns the
    # window never asks about (their answers fill the cache and stay unused)
    t = time.perf_counter()
    noise = lake.noise()
    i = 0
    for _ in range(WARM_ROUNDS):
        for b in buckets:
            futs = [scheduler.submit(request(int(noise[(i + j) % noise.size])))
                    for j in range(b)]
            i += b
            for f in futs:
                f.result(timeout=600)
    parts["warm_traffic_s"] = time.perf_counter() - t
    return Rig(cell=cell, seed=seed, lake=lake, trees=trees, engine=engine,
               scheduler=scheduler, catalog_dir=catalog_dir, buckets=buckets,
               setup_parts=parts)


def teardown(rig: Rig) -> None:
    rig.scheduler.close()
    rig.engine.close()
    shutil.rmtree(rig.catalog_dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------

@dataclasses.dataclass(eq=False)
class Rec:
    qid: int
    due: float = 0.0
    submit: float = 0.0
    done: float | None = None
    future: object = None
    error: BaseException | None = None

    def response(self):
        if self.error is not None or self.future is None or self.done is None:
            return None
        try:
            return self.future.result(timeout=0)
        except Exception as e:           # the request failed in the program
            self.error = e
            return None


class CompileCounter:
    """Compilations (fresh or from the persistent cache) while armed."""

    NAMES = ("/jax/core/compile/backend_compile_duration",
             "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.armed and event in self.NAMES:
            self.count += 1


class Tracer:
    """Profiler trace of a ``--trace 1`` run, with the layers' entry points
    wrapped in ``bench.*`` host spans. The ``bench.window`` span covers
    the measured window; the trace stops only once the window's answers
    are in, so writing it out stalls no request."""

    def __init__(self, rig: Rig, trace_dir: str):
        import jax

        self.jax = jax
        self.dir = trace_dir
        self._span = None
        sch, eng = rig.scheduler, rig.engine
        sch._next_batch = self._wrap("bench.scheduler.wait", sch._next_batch)
        sch._run_batch = self._wrap("bench.scheduler.batch", sch._run_batch)
        eng.query_batch = self._wrap("bench.engine.query_batch",
                                     eng.query_batch)
        ex = eng._executor
        ex.execute = self._wrap("bench.executor.execute", ex.execute)

    def _wrap(self, name, fn):
        ann = self.jax.profiler.TraceAnnotation

        @functools.wraps(fn)
        def wrapped(*a, **kw):
            with ann(name):
                return fn(*a, **kw)
        return wrapped

    def start(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
        # device ops and TraceMe spans only: the Python tracer, on by
        # default, records every Python call and slows the host path
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = self.jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()

    def close_window(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def stop(self) -> None:
        self.close_window()
        self.jax.profiler.stop_trace()


class GcClock:
    """Time the interpreter's garbage collector takes while armed."""

    def __init__(self):
        self.total = self.worst = 0.0
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.total += d
            self.worst = max(self.worst, d)
            self._t = None

    def close(self) -> None:
        gc.callbacks.remove(self._on)


@dataclasses.dataclass
class Window:
    recs: list
    t0: float
    seconds: float
    lateness_ms: list
    sched_before: dict
    sched_after: dict
    engine_before: dict
    engine_after: dict
    dispatch_before: dict
    dispatch_after: dict
    compiles: int
    gc_s: float = 0.0            # garbage collection inside the window
    gc_worst_s: float = 0.0

    def completed_in_window(self) -> int:
        end = self.t0 + self.seconds
        return sum(1 for r in self.recs if r.done is not None
                   and r.done <= end and r.error is None)


def _done(rec: Rec, sink, fut) -> None:
    rec.done = time.perf_counter()
    if sink is not None:
        sink.put(rec)


def _submit(rig: Rig, rec: Rec, req, sink=None) -> None:
    rec.submit = time.perf_counter()
    try:
        rec.future = rig.scheduler.submit(req)
    except Exception as e:              # shed or closed: never answered
        rec.error = e
        rec.done = None
        if sink is not None:
            sink.put(rec)
        return
    rec.future.add_done_callback(functools.partial(_done, rec, sink))


def drive_open(rig: Rig, sched: trafficgen.Schedule, seconds: float,
               tracer: Tracer | None) -> tuple[list, float]:
    reqs = [request(int(q)) for q in sched.qids]
    recs = [Rec(qid=int(q)) for q in sched.qids]
    if tracer is not None:
        tracer.start()
    t0 = time.perf_counter() + 0.01
    for rec, req, due in zip(recs, reqs, sched.due):
        rec.due = t0 + float(due)
        wait = rec.due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        _submit(rig, rec, req)
    left = t0 + seconds - time.perf_counter()
    if left > 0:
        time.sleep(left)
    if tracer is not None:
        tracer.close_window()
    return recs, t0


def drive_closed(rig: Rig, qids: np.ndarray, in_flight: int, seconds: float,
                 tracer: Tracer | None) -> tuple[list, float]:
    sink: queue.SimpleQueue = queue.SimpleQueue()
    recs: list[Rec] = []
    it = iter(qids)
    if tracer is not None:
        tracer.start()
    t0 = time.perf_counter()
    end = t0 + seconds
    outstanding = 0
    for _ in range(in_flight):
        q = int(next(it))
        rec = Rec(qid=q, due=t0)
        recs.append(rec)
        outstanding += 1
        _submit(rig, rec, request(q), sink)
    while outstanding:
        try:
            fin = sink.get(timeout=0.05)
        except queue.Empty:
            fin = None
        now = time.perf_counter()
        if now >= end and tracer is not None:
            tracer.close_window()
        if fin is None:
            if now > end + GRACE_S:
                break
            continue
        outstanding -= 1
        if now < end:
            q = int(next(it))
            rec = Rec(qid=q, due=fin.done if fin.done is not None else now)
            recs.append(rec)
            outstanding += 1
            _submit(rig, rec, request(q), sink)
    return recs, t0


def run_window(rig: Rig, seconds: float, *, trace_dir: str | None = None,
               rate: float | None = None, draw: int = 0) -> Window:
    """Drive the cell's traffic for ``seconds``. ``rate`` overrides the
    open loop's mean rate and ``draw`` picks the seed's query stream and
    the mix's arrival stream (the knee sweep runs several windows on one
    set-up)."""
    cell = rig.cell
    tr = cell.traffic
    rng = rng_for(rig.seed, 0x7AFF, draw)
    ids = trafficgen.pool(tr, rig.lake, rng)
    tracer = Tracer(rig, trace_dir) if trace_dir else None
    counter = CompileCounter()
    gc_clock = GcClock()
    ex = rig.engine._executor
    sb, eb, db = (rig.scheduler.stats(), rig.engine.stats(),
                  ex.dispatch_stats())
    # what set-up made (lake, model, engine state, JAX) is collected once
    # and frozen, so the window's collections scan only what it makes
    gc.collect()
    gc.freeze()
    counter.armed = True
    if tr["loop"] == "open":
        if rate is None:
            rate = (float(cell.config["knee_qps"])
                    * float(tr.get("rate_of_knee", 1.0)))
        sched = trafficgen.open_schedule(tr, rate, seconds, ids, rng,
                                         stream=draw)
        recs, t0 = drive_open(rig, sched, seconds, tracer)
    elif tr["loop"] == "closed":
        qids = trafficgen.stream(tr, ids, rng)
        recs, t0 = drive_closed(rig, qids, int(tr["in_flight"]), seconds,
                                tracer)
    else:
        raise ValueError(f"unknown loop {tr['loop']!r}")
    end = t0 + seconds
    deadline = end + GRACE_S
    for r in recs:
        if r.future is not None and r.done is None:
            left = deadline - time.perf_counter()
            try:
                r.future.result(timeout=max(left, 0.001))
            except Exception:
                pass
            if r.done is None:          # callback not yet run, or never
                r.done = time.perf_counter() if r.future.done() else None
    counter.armed = False
    gc_clock.close()
    gc.unfreeze()
    if tracer is not None:
        tracer.stop()
    sa, ea, da = (rig.scheduler.stats(), rig.engine.stats(),
                  ex.dispatch_stats())
    late = [(r.submit - r.due) * 1e3 for r in recs if r.submit]
    return Window(recs=recs, t0=t0, seconds=seconds, lateness_ms=late,
                  sched_before=sb, sched_after=sa, engine_before=eb,
                  engine_after=ea, dispatch_before=db, dispatch_after=da,
                  compiles=counter.count, gc_s=gc_clock.total,
                  gc_worst_s=gc_clock.worst)


# ---------------------------------------------------------------------------
# what a reader gets
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """Everything a metric reader may read, for one run of one cell."""

    cell: Cell
    window: Window
    setup_s: float
    verdict: dict
    trace: object = None
    peaks: dict | None = None

    def latencies_ms(self) -> np.ndarray:
        return np.asarray([(r.done - r.due) * 1e3 for r in self.window.recs
                           if r.done is not None and r.error is None])

    def responses(self) -> list:
        out = []
        for r in self.window.recs:
            resp = r.response()
            if resp is not None:
                out.append(resp)
        return out

    def delta(self, which: str, *keys) -> float:
        """Counter difference over the window, e.g.
        ``delta("sched", "batches")``."""
        a = getattr(self.window, f"{which}_after")
        b = getattr(self.window, f"{which}_before")
        for k in keys:
            a, b = a[k], b[k]
        return a - b


def quantile(values, q: float) -> float:
    """The ``q`` quantile by linear interpolation between order
    statistics (numpy's default), over every value."""
    return float(np.quantile(np.asarray(values, np.float64), q))


def stall_report(win: Window) -> str:
    """The longest wait between two answers inside the window, and the
    longest per-request span of each phase: where a slow run lost time."""
    end = win.t0 + win.seconds
    done = np.sort([r.done for r in win.recs
                    if r.done is not None and r.done <= end])
    gap, at = 0.0, 0.0
    if done.size > 1:
        i = int(np.argmax(np.diff(done)))
        gap, at = float(done[i + 1] - done[i]), float(done[i] - win.t0)
    worst: dict = {}
    for r in win.recs:
        resp = r.response()
        for sp in (resp.trace if resp is not None else ()):
            worst[sp["phase"]] = max(worst.get(sp["phase"], 0.0), sp["ms"])
    return (f"stalls: longest wait between answers {gap * 1e3:.1f} ms (at "
            f"+{at:.3f} s); longest span ms " + ", ".join(
                f"{k} {v:.1f}" for k, v in worst.items()))


# ---------------------------------------------------------------------------
# one whole run
# ---------------------------------------------------------------------------

def verdict_of(rig: Rig, win: Window) -> dict:
    """Sample, reference, judgement; program state already released."""
    cfg = rig.cell.config
    k = int(cfg["widths"]["k"])
    qs = check.sample([r.qid for r in win.recs],
                      int(cfg["reference"]["sample_queries"]), rig.seed)
    t = time.perf_counter()
    scan = reference.ExactScan(rig.lake, rig.trees,
                               block=int(cfg["reference"]["block_columns"]))
    t_profiles = time.perf_counter() - t
    scores = scan.scores(qs)
    chosen = set(qs.tolist())
    answers, unanswered = [], 0
    for r in win.recs:
        resp = r.response()
        if resp is None:
            unanswered += 1
            continue
        if r.qid in chosen:
            answers.append((r.qid, [(m.column_id, m.score)
                                    for m in resp.matches]))
    partners = check.sure_partners(rig.lake, qs, cfg["widths"])
    v = check.judge(answers, scores, qs, k, partners)
    v["unanswered"] = unanswered
    v["sample_queries"] = int(qs.size)
    v["reference_s"] = time.perf_counter() - t
    v["reference_profiles_s"] = t_profiles
    return v


def limits(cell: Cell) -> dict:
    return {k: float(v) for k, v in cell.config["limits"].items()}


def checks_of(cell: Cell, verdict: dict) -> dict:
    lim = limits(cell)
    return {name: {"value": verdict[name], "limit": lim[name]}
            for name in lim}


def is_correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def device_record(cell: Cell) -> dict:
    import jax

    devs = jax.devices()[:cell.chips]
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run(layout: Layout, cell_name: str, seed: int, seconds: float,
        traced: bool, t_start: float, hook=None) -> dict:
    """One run; returns the result line's object. ``hook(rig)``, given,
    runs after set-up (the tests break the timed path with it)."""
    cell = layout.cell(cell_name)
    rig = setup(cell, seed)
    if hook is not None:
        hook(rig)
    setup_s = time.perf_counter() - t_start
    log("setup: " + ", ".join(f"{k} {v:.3f}" for k, v in
                              rig.setup_parts.items())
        + f"; total {setup_s:.3f} s")
    trace_dir = os.path.join(WORK, "trace") if traced else None
    win = run_window(rig, seconds, trace_dir=trace_dir)
    device = device_record(cell)
    teardown(rig)
    late = win.lateness_ms or [0.0]
    worst = max(win.recs, key=lambda r: r.submit - r.due, default=None)
    log(f"window: {len(win.recs)} requests, {win.completed_in_window()} "
        f"completed in {seconds} s; generator lateness ms p50 "
        f"{quantile(late, 0.5):.4f} p99 {quantile(late, 0.99):.4f} max "
        f"{max(late):.4f} (at +"
        f"{(worst.due - win.t0) if worst else 0.0:.3f} s); garbage "
        f"collection {win.gc_s:.4f} s, longest {win.gc_worst_s:.4f} s; "
        f"compiles in window {win.compiles}; jit fallbacks "
        f"{win.dispatch_after['fallback'] - win.dispatch_before['fallback']}")
    lat = Run(cell=cell, window=win, setup_s=0.0, verdict={}).latencies_ms()
    if lat.size:
        log("latency ms: " + ", ".join(
            f"p{100 * q:g} {quantile(lat, q):.4f}"
            for q in (0.5, 0.9, 0.95, 0.99, 0.999)) + f", max {lat.max():.4f}")
    log(stall_report(win))
    trace = None
    if traced:
        trace = tracing.Trace.load(trace_dir)
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s()
    verdict = verdict_of(rig, win)
    checks = checks_of(cell, verdict)
    log(f"reference: {verdict['sample_queries']} sampled queries, "
        f"{verdict['n_answers']} answers ({verdict['n_matches']} columns) "
        f"judged in {verdict['reference_s']:.3f} s (profiles "
        f"{verdict['reference_profiles_s']:.3f} s); recall@k "
        f"{verdict['recall']:.6f}; answers missing a surely scored better "
        f"column {verdict['missed_candidates']!r}; widest score gap "
        f"{verdict['score_gap']!r}; scores off by more than "
        f"{check.SCORE_TOL}: {verdict['score_mismatch_share']!r}")
    run_ = Run(cell=cell, window=win, setup_s=setup_s, verdict=verdict,
               trace=trace,
               peaks=layout.peaks(device["kind"]) if traced else None)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        kind = "metrics" if traced else "end_to_end"
        value = layout.reader(kind, m["name"]).read(run_)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out = {"correct": is_correct(checks),
           "attempted": len(win.recs),
           "failed": verdict["unanswered"],
           "metrics": metrics, "device": device}
    if traced:
        out["breakdown"] = {"device_ops": trace.top_ops(),
                            "idle_gaps": trace.idle_gaps()}
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return out
