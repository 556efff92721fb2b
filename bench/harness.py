"""One run of one cell: set-up, the open-loop window, the reference check.

The system under test is the program's served path:
``ServingTier.submit`` → ``IngestLoop`` → ``StreamRuntime`` ingest and
publish → ``SnapshotRing`` → ``ServeFrontend`` reads. Everything else here
belongs to the benchmark: the stream, the schedules, the stamps, the
reference and the reduction of the trace.

Threads during the window (all started and joined here):

  writer     submits block j of the replayed pool at its due time; it is
             timed from the due time, so a full admission queue shows as
             admission wait.
  readers    ``READER_THREADS`` threads share one read schedule; a read is
             timed from its due time to its host-materialized answer.
  watcher    on every new ring version, materializes it (summary and n)
             and stamps when that answer arrived: the freshness clock.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import shutil
import threading
import time
from pathlib import Path

import numpy as np

from bench import checks, schedule, stream
from bench.oracle import Oracle

LEAD_IN_S = 1.0          # load runs this long before the window opens
READER_THREADS = 4
POINT_IDS = 64           # ids per point read
TOP_N = 100              # rows per top read
JOIN_S = 60.0            # a minute past the close for late answers


def log(tag: str, **fields) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


@dataclasses.dataclass
class Cell:
    """A workload of BENCHMARK.json with the files it names, loaded."""
    name: str
    chips: int
    config: dict
    mix: dict
    offered: dict            # bench/cells/<name>.json

    @property
    def shards(self) -> int:
        return int(self.config["shards"])

    @property
    def block(self) -> int:
        """Ids per submitted block: one chunk per worker."""
        return int(self.config["lanes"]) * self.shards * int(
            self.config["chunk"])


class CompileClock:
    """Process-wide backend compiles, from ``jax.monitoring`` events."""

    def __init__(self):
        import jax.monitoring as mon
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += secs
                self.compiles += 1

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1


class GcClock:
    """The process's garbage collections, each as (generation, start,
    seconds), from ``gc.callbacks`` while it is open."""

    def __init__(self):
        self.pauses: list[tuple[int, float, float]] = []
        self._t = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"], self._t,
                                time.perf_counter() - self._t))
            self._t = None

    def close(self):
        gc.callbacks.remove(self._on_gc)

    def log(self, t_open: float, t_close: float) -> None:
        inside = [p for p in self.pauses if t_open <= p[1] < t_close]
        full = [p for p in inside if p[0] == 2]
        log("gc", collections=len(inside), full=len(full),
            max_ms=f"{max((p[2] for p in inside), default=0.0) * 1e3:.4f}",
            total_ms=f"{sum(p[2] for p in inside) * 1e3:.4f}")


def _annotate(enabled: bool, name: str):
    """A profiler annotation in traced runs, nothing otherwise."""
    if not enabled:
        return contextlib.nullcontext()
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


# -- building the tier --------------------------------------------------------

def serve_config(cell: Cell, out_dir: Path):
    """The ServeConfig of a cell: the configuration fixes k, lanes and
    shards; every other knob stays on the program's own resolution."""
    from repro.plan import planned_engine_config
    from repro.runtime import RuntimeConfig
    from repro.serve import ServeConfig

    cfg = cell.config
    engine = planned_engine_config(int(cfg["k"]), tenants=int(cfg["lanes"]),
                                   kernel="auto")
    if engine.chunk != int(cfg["chunk"]):
        raise RuntimeError(
            f"the program's planned chunk is {engine.chunk}, the "
            f"configuration assumes {cfg['chunk']}: blocks would be padded")
    runtime = RuntimeConfig(engine=engine, shards=cell.shards,
                            reduction="auto")
    return ServeConfig(runtime=runtime,
                       flight_path=str(out_dir / "flight_record.json"))


def resolved_knobs(cfg, runtime, tier) -> dict:
    from repro.kernels import ops
    from repro.plan import active_plan
    k = cfg.runtime.engine.k
    return {
        "impls": ",".join(f"{op}:{ops.resolve_impl(op, k)}"
                          for op in ("update", "combine", "query", "flush")),
        "reduction": runtime.engine.config.reduction,
        "chunk": cfg.runtime.engine.chunk,
        "depth": cfg.runtime.engine.buffer_depth,
        "publish_every": tier.publish_every,
        "coalesce_max": tier.coalesce_max,
        "feed_depth": tier.feed_depth,
        "lazy_publish": tier.lazy_publish,
        "ring_depth": tier.ring.depth,
        "queue_depth": cfg.queue_depth,
        "admission": cfg.admission,
        "query_min_batch": active_plan().query_min_batch,
        "plan": active_plan().source,
    }


def warm_up(cfg, runtime, pool: stream.Pool, reads: "ReadMix") -> None:
    """Every program the window runs, compiled on a tier that shares the
    runtime: plain and donated ingest, the publish reduction, and each
    read at the shapes the mix sends."""
    from repro.serve import ServingTier
    with ServingTier(cfg, runtime=runtime) as warm:
        for j in range(warm.publish_every + 2):
            warm.submit(pool.block_at(j))
        warm.drain()
        for op in ("point", "top", "kmaj"):
            reads.perform(warm.frontend, op, reads.point_ids(0))


# -- the read mix -------------------------------------------------------------

class ReadMix:
    """The mix's read operations, materialized on the host."""

    def __init__(self, mix: dict, config: dict, rng: np.random.Generator):
        self.k = int(config["k"])
        self.skew = float(config["skew"])
        self.max_id = int(config["max_id"])
        self.shares = mix["read_shares"]
        self._rng = rng
        self._ids: list[np.ndarray] = []

    def draw_ids(self, count: int) -> None:
        """Point-read ids, drawn up front from the cell's own Zipf."""
        raw = self._rng.zipf(self.skew, size=(count, POINT_IDS))
        self._ids = list(stream.fold_ids(raw, self.max_id).astype(np.int32))

    def point_ids(self, i: int) -> np.ndarray:
        return self._ids[i] if self._ids else np.arange(
            1, POINT_IDS + 1, dtype=np.int32)

    def perform(self, frontend, op: str, ids: np.ndarray) -> dict:
        """One read through the served frontend; the answer, on the host."""
        if op == "point":
            est = frontend.estimate(ids)
            return {"n": est.n, "version": est.version, "ids": ids,
                    "f_hat": est.f_hat, "lower": est.lower}
        if op == "top":
            t = frontend.top_table(TOP_N)
            rows = t.rows
            return {"n": t.n, "version": t.version, "asked": TOP_N,
                    "items": np.array([r["item"] for r in rows], np.int64),
                    "counts": np.array([r["count"] for r in rows], np.int64),
                    "lower": np.array([r["lower"] for r in rows], np.int64)}
        if op == "kmaj":
            rep = frontend.k_majority_report(self.k)
            return {"n": rep.n, "version": rep.version,
                    "threshold": rep.threshold,
                    "candidates": rep.candidate_items,
                    "guaranteed": rep.guaranteed_items}
        raise ValueError(f"unknown read op {op!r}")


# -- what the tier published ---------------------------------------------------

class Published:
    """Every snapshot the tier's ring receives, by version, so that each
    read can be recomputed from the summary it was answered from.

    It holds references only: nothing is copied to the host until the
    window has closed (:meth:`host`)."""

    def __init__(self, ring):
        self._snaps: dict = {}
        self._host: dict = {}
        publish = ring.publish

        def record(snap):
            self._snaps[snap.version] = snap
            return publish(snap)

        ring.publish = record

    def host(self, version: int) -> dict | None:
        """The summary and n of ``version`` on the host (None if the ring
        never received it)."""
        if version not in self._host:
            snap = self._snaps.get(version)
            if snap is None:
                return None
            s = snap.summary
            self._host[version] = {
                "items": np.asarray(s.items), "counts": np.asarray(s.counts),
                "errors": np.asarray(s.errors), "n": int(snap.n)}
        return self._host[version]


# -- the window ---------------------------------------------------------------

class Window:
    """The open-loop load of one run and everything it stamps."""

    def __init__(self, cell: Cell, tier, pool: stream.Pool, reads: ReadMix,
                 *, seed: int, seconds: float, annotate: bool):
        self.cell, self.tier, self.pool, self.reads = cell, tier, pool, reads
        self.published = Published(tier.ring)
        self.seconds = float(seconds)
        self.annotate = annotate
        span = LEAD_IN_S + self.seconds
        block_rate = float(cell.offered["write_items_per_s"]) / cell.block
        self.w_due = schedule.poisson_due(
            block_rate, span, np.random.default_rng([seed, 2]))
        rrng = np.random.default_rng([seed, 3])
        self.r_due = schedule.poisson_due(float(cell.mix["reads_per_s"]),
                                          span, rrng)
        self.r_ops = schedule.shuffled_mix(reads.shares, len(self.r_due),
                                           rrng)
        reads.draw_ids(len(self.r_due))
        self.w_call = np.full(len(self.w_due), np.nan)
        self.w_ret = np.full(len(self.w_due), np.nan)
        self.r_done = np.full(len(self.r_due), np.nan)
        self.r_answer: list = [None] * len(self.r_due)
        self.stamps: list[tuple[float, int, int]] = []   # (t, version, n)
        self.errors: list[BaseException] = []
        self.final = None
        self._next_read = 0
        self._lock = threading.Lock()
        self._stop_watch = threading.Event()
        self.t0 = self.t_open = self.t_close = 0.0

    def _guard(self, body):
        def run():
            try:
                body()
            except BaseException as e:      # re-raised by the main thread
                self.errors.append(e)
        return run

    def _writer(self):
        submit = self.tier.submit
        for j, due in enumerate(self.w_due):
            t_due = self.t0 + due
            if t_due >= self.t_close or time.perf_counter() >= self.t_close:
                return
            wait = t_due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            block = self.pool.block_at(j)
            self.w_call[j] = time.perf_counter()
            with _annotate(self.annotate, "bench.submit"):
                submit(block)
            self.w_ret[j] = time.perf_counter()

    def _reader(self):
        frontend = self.tier.frontend
        while True:
            with self._lock:
                i = self._next_read
                self._next_read += 1
            if i >= len(self.r_due) or self.t0 + self.r_due[i] >= self.t_close:
                return
            wait = self.t0 + self.r_due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            op = self.r_ops[i]
            try:
                with _annotate(self.annotate, f"bench.read.{op}"):
                    ans = self.reads.perform(frontend, op,
                                             self.reads.point_ids(i))
            except Exception as e:      # a read that fails never answers
                log("read-error", op=op, error=repr(e))
                continue
            self.r_done[i] = time.perf_counter()
            self.r_answer[i] = ans

    def _watcher(self):
        import jax
        ring = self.tier.ring
        last = 0
        while not self._stop_watch.is_set():
            try:
                snap = ring.wait_for(last + 1, timeout=0.05)
            except TimeoutError:
                continue
            with _annotate(self.annotate, "bench.watch"):
                jax.block_until_ready(snap.summary)
                n = int(snap.n)
            self.stamps.append((time.perf_counter(), snap.version, n))
            last = snap.version

    def run(self, mark, during=None) -> None:
        """Drive the load: lead-in, window, close, drain. ``mark(name)``
        is called as the window opens and closes, and ``during(self)``
        on the main thread while it is open."""
        self.t0 = time.perf_counter() + 0.05
        self.t_open = self.t0 + LEAD_IN_S
        self.t_close = self.t_open + self.seconds
        load = ([threading.Thread(target=self._guard(self._writer),
                                  name="bench-writer")]
                + [threading.Thread(target=self._guard(self._reader),
                                    name=f"bench-reader-{i}")
                   for i in range(READER_THREADS)])
        watcher = threading.Thread(target=self._guard(self._watcher),
                                   name="bench-watcher")
        for t in [watcher] + load:
            t.start()
        try:
            time.sleep(max(0.0, self.t_open - time.perf_counter()))
            mark("open")
            if during is not None:
                during(self)
            time.sleep(max(0.0, self.t_close - time.perf_counter()))
            mark("close")
            for t in load:
                t.join(max(1.0, JOIN_S - (time.perf_counter()
                                          - self.t_close)))
            if not self.errors and not any(t.is_alive() for t in load):
                # the watcher sees the drained position before it stops
                self.final = self.tier.drain(timeout=JOIN_S)
                self.tier.loop.sync()
                deadline = time.perf_counter() + JOIN_S
                while (not self.stamps
                       or self.stamps[-1][1] < self.final.version) \
                        and time.perf_counter() < deadline \
                        and watcher.is_alive():
                    time.sleep(0.005)
        finally:
            self._stop_watch.set()
            watcher.join(JOIN_S)
        alive = [t.name for t in [watcher] + load if t.is_alive()]
        if self.errors:
            raise RuntimeError("a load thread failed") from self.errors[0]
        if alive:
            raise RuntimeError(f"threads did not stop: {alive}")


# -- one run ------------------------------------------------------------------

def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             root: Path, t_start: float) -> dict:
    """One run of a cell: what it measured, and the readings of the
    reference check."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs import trace as obs_trace
    from repro.runtime import StreamRuntime
    from repro.serve import ServingTier

    out_dir = root / "bench" / "_out" / cell.name
    out_dir.mkdir(parents=True, exist_ok=True)
    cache = enable_compile_cache()
    clock = CompileClock()
    cfg = serve_config(cell, out_dir)
    runtime = StreamRuntime(cfg.runtime)

    t = time.perf_counter()
    pool = stream.Pool(stream.zipf_stream(
        int(cell.config["n_items"]), float(cell.config["skew"]),
        seed=seed, max_id=int(cell.config["max_id"])), cell.block)
    log("stream", pool_ids=pool.size, block=cell.block,
        gen_s=f"{time.perf_counter() - t:.3f}")

    reads = ReadMix(cell.mix, cell.config, np.random.default_rng([seed, 1]))
    t = time.perf_counter()
    warm_up(cfg, runtime, pool, reads)
    log("warmup", s=f"{time.perf_counter() - t:.3f}",
        compile_s=f"{clock.seconds:.3f}", compiles=clock.compiles,
        cache_hits=clock.cache_hits, cache_dir=cache)

    tracer = obs_trace.Tracer(annotate=True) if trace else None
    tier = ServingTier(cfg, runtime=runtime, tracer=tracer)
    log("knobs", **resolved_knobs(cfg, runtime, tier))
    window = Window(cell, tier, pool, reads, seed=seed, seconds=seconds,
                    annotate=trace)
    step = tier.registry.histogram("serve.ingest.step_s")
    blocks = tier.registry.counter("serve.ingest.blocks")
    marks: dict = {}

    def mark(name: str):
        marks[name] = (step.raw(), blocks.value, clock.compiles)

    trace_dir = out_dir / f"trace-{seed}"
    traced: dict = {}
    items = tier.registry.counter("serve.ingest.items")

    def during(w: Window):
        slice_s = min(float(cell.mix["trace_slice_s"]), w.seconds / 2)
        time.sleep(max(0.0, w.t_open + (w.seconds - slice_s) / 2
                       - time.perf_counter()))
        shutil.rmtree(trace_dir, ignore_errors=True)
        # host spans and launches only: the Python tracer would record
        # every function call and slow the host path it measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            with _annotate(True, "bench.slice"):
                traced["t0"] = time.perf_counter()
                traced["items0"] = items.value
                time.sleep(slice_s)
                traced["items1"] = items.value
                traced["t1"] = time.perf_counter()
        finally:
            jax.profiler.stop_trace()

    # what set-up built (the program's compiled and traced objects, the
    # pool) lives through the window: moved out of the collector's reach,
    # a full collection in the window walks only what the window
    # allocates, instead of pausing every thread to walk it all
    gc.collect()
    gc.freeze()
    gcs = GcClock()
    tier.start()
    try:
        window.run(mark, during if trace else None)
    finally:
        tier.stop(drain=False)
        gcs.close()
        gc.unfreeze()
    setup_s = window.t_open - t_start
    gcs.log(window.t_open, window.t_close)

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()[:cell.chips]]
    peak = max((p for p in peaks if p is not None), default=None)
    log("memory", peak_bytes_in_use=peak, per_device=peaks)

    measured = measure(cell, window, marks)
    measured["setup_s"] = setup_s
    readings = check_run(cell, window)
    return {"measured": measured, "readings": readings,
            "memory_peak_bytes": peak, "trace_dir": trace_dir if trace
            else None, "traced": traced, "window": window}


def measure(cell: Cell, w: Window, marks: dict) -> dict:
    """End-to-end and host-side numbers of the window, with the earlier
    lines that explain them."""
    stamp_t = np.array([s[0] for s in w.stamps])
    stamp_n = np.array([s[2] for s in w.stamps], dtype=np.int64)
    n_open = schedule.count_at(w.t_open, stamp_t, stamp_n)
    n_close = schedule.count_at(w.t_close, stamp_t, stamp_n)

    w_due = w.t0 + w.w_due
    sent = ~np.isnan(w.w_ret)
    in_win = (w_due >= w.t_open) & (w_due < w.t_close) & sent
    ends = (np.arange(w_due.size) + 1) * cell.block
    fresh = schedule.freshness(w_due[in_win], ends[in_win], stamp_t, stamp_n)
    adm_wait = w.w_ret[in_win] - w_due[in_win]
    prev_ret = np.concatenate([[-np.inf], w.w_ret[:-1]])
    late = schedule.own_lateness(w_due[sent], w.w_call[sent], prev_ret[sent])

    r_due = w.t0 + w.r_due
    r_in = (r_due >= w.t_open) & (r_due < w.t_close)
    r_lat = np.where(np.isnan(w.r_done), np.inf, w.r_done - r_due)[r_in]

    (_, step_o, _), blk_o, comp_o = marks["open"]
    (_, step_c, _), blk_c, comp_c = marks["close"]
    tenth = max(1, adm_wait.size // 10)
    out = {
        "ingest_items_per_s": (n_close - n_open) / (w.t_close - w.t_open),
        "freshness_p99_ms": schedule.percentile(fresh, 99) * 1e3,
        "freshness_p50_ms": schedule.percentile(fresh, 50) * 1e3,
        "read_p99_ms": schedule.percentile(r_lat, 99) * 1e3,
        "read_p50_ms": schedule.percentile(r_lat, 50) * 1e3,
        "admission_wait_p99_ms": schedule.percentile(adm_wait, 99) * 1e3,
        "host_us_per_block": ((step_c - step_o) / (blk_c - blk_o) * 1e6
                              if blk_c > blk_o else float("nan")),
        # the backlog's trend: admission wait of the window's first and
        # last tenth of blocks (medians)
        "admission_wait_first_ms": float(np.median(adm_wait[:tenth])) * 1e3
        if adm_wait.size else float("nan"),
        "admission_wait_last_ms": float(np.median(adm_wait[-tenth:])) * 1e3
        if adm_wait.size else float("nan"),
        "blocks_acked": int(sent.sum()),
        "reads_due": int(r_in.sum()),
        "reads_failed": int(np.isinf(r_lat).sum()),
    }
    ms = lambda v, q: f"{schedule.percentile(v, q) * 1e3:.4f}"  # noqa: E731
    log("window", seconds=f"{w.t_close - w.t_open:.6f}", n_open=n_open,
        n_close=n_close, blocks_due=int(in_win.sum()),
        blocks_acked=int(sent.sum()), versions=len(w.stamps),
        reads_due=int(r_in.sum()), reads_failed=out["reads_failed"],
        compiles_in_window=comp_c - comp_o)
    log("writer", offered_items_per_s=cell.offered["write_items_per_s"],
        lateness_p99_ms=ms(late, 99),
        lateness_max_ms=f"{(late.max() if late.size else 0.0) * 1e3:.4f}",
        admission_wait_p50_ms=ms(adm_wait, 50),
        admission_wait_p99_ms=ms(adm_wait, 99),
        admission_wait_first_ms=f"{out['admission_wait_first_ms']:.4f}",
        admission_wait_last_ms=f"{out['admission_wait_last_ms']:.4f}")
    log("tails", freshness_p50_ms=ms(fresh, 50),
        freshness_p99_ms=ms(fresh, 99), freshness_samples=fresh.size,
        read_p50_ms=ms(r_lat, 50), read_p99_ms=ms(r_lat, 99),
        read_samples=r_lat.size,
        ingest_items_per_s=f"{out['ingest_items_per_s']:.1f}",
        host_us_per_block=f"{out['host_us_per_block']:.3f}")
    return out


def check_run(cell: Cell, w: Window) -> dict:
    """Readings of the final snapshot and of every read due in the
    window, against the exact counts."""
    t = time.perf_counter()
    oracle = Oracle(w.pool.ids)
    k = int(cell.config["k"])
    acked = int((~np.isnan(w.w_ret)).sum()) * cell.block
    if w.final is None:
        raise RuntimeError("the run ended with no drained snapshot")
    s = w.final.summary
    readings = checks.check_summary(
        oracle, np.asarray(s.items), np.asarray(s.counts),
        np.asarray(s.errors), n=int(w.final.n), acked=acked, k=k)
    r_due = w.t0 + w.r_due
    wrong = mismatched = unanswered = 0
    for i, op in enumerate(w.r_ops):
        if not w.t_open <= r_due[i] < w.t_close:
            continue
        ans = w.r_answer[i]
        if ans is None:
            unanswered += 1
            continue
        wrong += checks.read_is_wrong(oracle, op, ans)
        own = w.published.host(ans["version"])
        mismatched += own is None or checks.read_differs(op, ans, own, k=k)
    readings["reads_wrong"] = wrong
    readings["reads_mismatched"] = mismatched
    readings["reads_unanswered"] = unanswered
    n = int(w.final.n)
    log("reference", s=f"{time.perf_counter() - t:.3f}", n=n, acked=acked,
        max_error=int(np.asarray(s.errors).max()), n_per_k=n // k)
    return readings
