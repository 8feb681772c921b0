"""One run of one cell: set up, measure a window, check every answer.

Set-up makes the corpus on the device from the seed, loads it into
``VectorDB`` as the configuration states, and warms the plan buckets the
cell's traffic uses (past the ADC autotuner's probes). The window drives
``AsyncQueryEngine.submit`` in front of that database through a thin proxy
that times each ``db.query`` call. After the window the program's state
is freed, the corpus is made again from the seed, and the plain reference
judges every answer the window was due.
"""
from __future__ import annotations

import gc
import math
import os
import sys
import tempfile
import time

import jax
import numpy as np

from harness import data, drive, reference, trace as tr
from harness.spec import HERE, read_json



def enable_cache() -> str:
    """The program's persistent compile cache, holding every program, so
    that only a cell's first run in a checkout compiles."""
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


class CompileClock:
    """Counts JAX's backend compiles and persistent-cache hits/misses."""

    def __init__(self):
        from jax import monitoring
        self.seconds, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def line(self) -> str:
        return (f"compile {self.seconds:.1f}s in {self.compiles} programs "
                f"(cache hits {self.hits}, misses {self.misses})")


class DBProxy:
    """Forwards everything to the database and records each ``query``
    call: host start, host end, rows dispatched (the plan bucket)."""

    def __init__(self, db):
        self._db = db
        self.calls = []

    def __getattr__(self, name):
        return getattr(self._db, name)

    def query(self, q, k=10, **kw):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.db_query"):
            out = self._db.query(q, k=k, **kw)
        self.calls.append((t, time.perf_counter(), int(np.shape(q)[0])))
        return out


class Run:
    """What a metric reader may look at. Per-layer readers run while the
    database is loaded; end-to-end readers run after the reference, when
    ``db`` is gone and ``setup_s`` and ``checks`` are set."""

    def __init__(self, cell, db, win, queries, trace, peaks, kind, t_window):
        self.cell, self.db, self.window, self.queries = cell, db, win, queries
        self.trace, self.peaks, self.device_kind = trace, peaks, kind
        self.t_window = t_window  # host clock at the trace's window start
        self.calls = list(db.calls)  # the proxy is made for the window
        self.setup_s, self.checks = None, None

    def latencies_ms(self) -> np.ndarray:
        """Due to answered, per query sent; inf where none came."""
        w = self.window
        lat = (w.done[:w.sent] - w.due[:w.sent]) * 1e3
        return np.where(np.isfinite(lat), lat, np.inf)

    def pending_ns(self):
        """Intervals (trace clock, ns) in which some query of the window
        was due and not yet answered."""
        w = self.window
        done = np.where(np.isfinite(w.done[:w.sent]), w.done[:w.sent],
                        np.nanmax(w.done[:w.sent], initial=w.t1))
        off = self.trace.t0 - self.t_window * 1e9
        return list(zip(w.due[:w.sent] * 1e9 + off, done * 1e9 + off))


class GCClock:
    """Python's garbage collections while it is on: how many of each
    generation and the longest pause (a stall of the whole host side)."""

    def __init__(self):
        self.pauses, self._t = [], None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))
            self._t = None

    def close(self) -> str:
        gc.callbacks.remove(self._on)
        gens = [sum(g == n for g, _ in self.pauses) for n in range(3)]
        worst = max((p for _, p in self.pauses), default=0.0)
        return f"gc {gens} by generation, longest {worst * 1e3:.1f} ms"


def nearest_rank(values, p: float) -> float:
    v = np.sort(np.asarray(values, float))
    return float(v[max(0, math.ceil(p * len(v)) - 1)]) if len(v) else math.nan


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def cell_shape(cell, rehearse: bool = False) -> data.Shape:
    """The corpus the cell serves; rehearsed, the configuration's tiny
    ``rehearsal`` corpus for the CPU (its rows and cluster sizes)."""
    conf = cell.config
    gen, rows = conf["generator"], conf["rows"]
    if rehearse:
        gen = dict(gen, **conf["rehearsal"])
        rows = gen.pop("rows")
    return data.Shape(rows, conf["dim"], gen)


def make_db(cell, x):
    from repro.core import VectorDB
    ix = dict(cell.config["index"])
    engine = ix.pop("engine")
    db = VectorDB(engine, metric=cell.config["metric"], **ix)
    t = time.perf_counter()
    db.load(x)
    idx = db.index
    jax.block_until_ready((idx.codes_bm, idx.corpus))
    log(f"load {time.perf_counter() - t:.1f}s | {idx.centroids.shape[0]} "
        f"lists, {idx.codes_bm.shape[0]} blocks of {idx.codes_bm.shape[1]},"
        f" steps/probe {idx.spp}, largest list {int(idx.layout.bcnt.max())} "
        f"blocks | re-rank rows {idx.corpus.nbytes / 1e9:.2f} GB")
    return db


def warm_up(db, cell, q: np.ndarray, k: int, passes: int = 12):
    """Compile (or read from the cache) every plan bucket the window will
    use, and repeat until a pass over them takes no ADC autotuner probe."""
    for _ in range(passes):
        before = (db.adc_stats or {}).get("probes", 0)
        for b in cell.front["warm_buckets"]:
            jax.block_until_ready(db.query(q[:b], k=k))
        if (db.adc_stats or {}).get("probes", 0) == before:
            break


def window_queries(cell, shape, seed: int, seconds: float):
    """(queries, arrival offsets, warm-up queries) for the cell."""
    t = cell.traffic
    if t["loop"] != "open":
        raise ValueError(f"unknown traffic loop {t['loop']!r}")
    rng = np.random.default_rng(seed)
    # one arrival schedule for every seed: which gaps fall together decides
    # the batches, and so the tail, far more than the data does
    n = data.n_arrivals(cell.front["rate_qps"], seconds)
    gaps = data.exp_gaps(n, cell.front["rate_qps"],
                         np.random.default_rng(t["arrival_seed"]))
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    subs = data.topic_subs(shape, t["topic"], n, rng)
    warm = data.topic_subs(shape, t["topic"], max(cell.front["warm_buckets"]),
                           rng)
    return (data.make_queries(shape, seed, subs, stream=1), arrivals,
            data.make_queries(shape, seed, warm, stream=0))


def run_cell(cell, seed: int, seconds: float, trace_on: bool, t_start: float,
             *, rehearse: bool = False, proxy=DBProxy,
             control: bool = False) -> dict:
    from repro.serve.async_engine import AsyncQueryEngine

    clock = CompileClock()
    dev = jax.devices()[0]
    conf, k = cell.config, int(cell.traffic["k"])
    shape = cell_shape(cell, rehearse)
    x = data.make_corpus(shape, seed)
    log(f"corpus {shape.rows} x {conf['dim']} f32 ({x.nbytes / 1e9:.2f} GB), "
        f"{shape.n_top} topics, {shape.n_sub} sub-centres | {clock.line()}")
    db = make_db(cell, x)
    del x
    queries, arrivals, warm = window_queries(cell, shape, seed, seconds)
    warm_up(db, cell, warm, k)
    log(f"warm-up done | {clock.line()} | adc {db.adc_stats}")

    px = proxy(db)
    f = cell.front
    eng = AsyncQueryEngine(px, max_batch=f["max_batch"],
                           max_wait_ms=f["max_wait_ms"],
                           max_queue=f["max_queue"],
                           max_inflight=f["max_inflight"])
    adc_before = db.adc_stats
    compiles_before = clock.compiles
    # a full collection now, so that no full pass over what set-up made
    # falls due inside the window, where a run's place in the collector's
    # count would decide whether one comes
    gc.collect()
    gcc = GCClock()
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        if trace_on:
            tr.start(tdir)
        with jax.profiler.TraceAnnotation("bench.window"):
            t_window = time.perf_counter()
            win = drive.open_loop(eng, queries, arrivals, k)
        eng.close()
        trace = None
        if trace_on:
            jax.profiler.stop_trace()
            trace = tr.Trace.from_dir(tdir) if dev.platform != "cpu" else None
    probes = ((db.adc_stats or {}).get("probes", 0)
              - (adc_before or {}).get("probes", 0))
    log(f"window {win.t1 - win.t0:.2f}s, {win.sent} sent, "
        f"{len(px.calls)} batches | compiles in window "
        f"{clock.compiles - compiles_before} | autotuner probes in window "
        f"{probes} | sent late by up to {max(win.late, default=0) * 1e3:.1f}"
        f" ms | {gcc.close()}")
    peaks = read_json(os.path.join(HERE, "peaks.json"))
    run = Run(cell, px, win, queries[:win.sent], trace, peaks,
              dev.device_kind, t_window)
    layer = {}
    if trace_on:
        for m in cell.per_layer:
            v = cell.reader(m["name"])(run)
            if v is not None:
                layer[m["name"]] = {"value": v, "unit": m["unit"]}
    peak_bytes = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    answers = win.answers()
    run.db = None
    del px, eng, db, adc_before
    gc.collect()

    # the reference, on the program's state freed
    t_ref = time.perf_counter()
    x = data.make_corpus(shape, seed)
    q = queries[:win.sent]
    _, ref_ids = reference.scan_topk(x, q, k)
    checks = reference.compare(answers, x, q, k, ref_ids)
    if control:  # the reference one precision lower, in the program's place
        low = reference.scan_topk(x, q, k, low=True)
        control_checks = reference.compare(list(zip(*low)), x, q, k, ref_ids)
    del x
    log(f"reference over {len(q)} queries: {time.perf_counter() - t_ref:.1f}s")

    run.setup_s, run.checks = t_window - t_start, checks
    metrics = layer if trace_on else {
        m["name"]: {"value": cell.reader(m["name"])(run), "unit": m["unit"]}
        for m in cell.end_to_end}
    limits = f["limits"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": peak_bytes}
    out = {"correct": passes(checks, limits), "attempted": int(win.sent),
           "failed": int(checks["unanswered"] + checks["malformed"]),
           "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        out["breakdown"] = {"device_ops": trace.top_modules(),
                            "idle_gaps": trace.idle_gaps()}
    if control:
        out["control"] = {"correct": passes(control_checks, limits),
                          "checks": control_checks}
    out["checks"] = {c: {"value": checks[c], "limit": limits[c]}
                     for c in limits}
    for c in limits:
        log(f"check {c} {checks[c]!r} limit {limits[c]!r}")
    return out


def passes(checks: dict, limits: dict) -> bool:
    """``correct``: every compared number at or under its limit."""
    return all(checks[c] <= limits[c] for c in limits)
