"""Observability layer: metrics exactness, trace spans, sketch health (§12).

Covers the obs layer's load-bearing contracts:

  * **exact under concurrency** — counters and histograms lose no
    updates under many writer threads (the bench's admission-closure
    gate depends on this), and IngestStats snapshots are never torn;
  * **conservative percentiles** — bucketized p50/p99 over-estimate by
    at most the recorded ``error_bound`` and never under-estimate;
  * **span semantics** — nesting (parent/depth) is tracked per thread,
    the event ring stays bounded, and the JSONL export round-trips;
  * **health ≡ invariants** — ``sketch_health`` agrees bitwise with the
    eval harness's oracle-free invariants on a seeded zipf stream, and
    the HealthMonitor refreshes gauges on ring publishes;
  * **the tier surface** — ``ServingTier.describe()`` exports metrics +
    health, reads land in per-op histograms, staleness is gauged, and
    the NULL instruments make ``metrics=False`` a true no-op.
"""
import json
import math
import threading
import time

import numpy as np
import pytest

from repro.data.synthetic import zipf_stream
from repro.engine import EngineConfig
from repro.obs import (Counter, Gauge, HealthMonitor, Histogram,
                       MetricsRegistry, Tracer, sketch_health)
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.runtime import RuntimeConfig, StreamRuntime, host_blocks
from repro.serve import IngestStats, ServeConfig, ServingTier, SnapshotRing

K, LANES, CHUNK, DEPTH = 64, 2, 128, 2


def _config(**kw):
    kw.setdefault("publish_every", 2)
    kw.setdefault("ring_depth", 3)
    return ServeConfig(runtime=RuntimeConfig(
        engine=EngineConfig(k=K, tenants=LANES, chunk=CHUNK,
                            buffer_depth=DEPTH, kernel="jnp"),
        shards=1), **kw)


def _blocks(rt, n_blocks, seed=0):
    return [zipf_stream(rt.workers * CHUNK, 1.1, seed=seed + i,
                        max_id=10**4) for i in range(n_blocks)]


# ---------------------------------------------------------------------------
# metrics: exactness, percentiles, registry, export
# ---------------------------------------------------------------------------

def test_counter_exact_under_concurrent_writers():
    c = Counter("t")
    n_threads, per = 8, 5000

    def work():
        for _ in range(per):
            c.inc()

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * per      # += would lose increments


def test_histogram_exact_count_under_concurrent_writers():
    h = Histogram("t")
    n_threads, per = 8, 2000

    def work(i):
        for j in range(per):
            h.record(1e-5 * (1 + i + j % 7))

    ts = [threading.Thread(target=work, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert h.count == n_threads * per


def test_histogram_percentile_conservative():
    h = Histogram("t")
    samples = [1.3e-4, 2.7e-4, 5.0e-4, 9.1e-4, 3.3e-3]
    for s in samples:
        h.record(s)
    for q in (50, 90, 99):
        exact = sorted(samples)[min(len(samples) - 1,
                                    math.ceil(q / 100 * len(samples)) - 1)]
        got = h.percentile(q)
        assert got >= exact                      # never under-estimates
        assert got <= exact * (1 + h.error_bound) + 1e-12
    assert h.percentile(99) <= max(samples)      # clamped to observed max
    d = h.describe()
    assert d["count"] == len(samples)
    assert d["max"] == max(samples)
    assert math.isclose(d["sum"], sum(samples))


def test_histogram_empty_is_nan():
    h = Histogram("t")
    assert math.isnan(h.percentile(50))
    assert math.isnan(h.describe()["p99"])


def test_registry_get_or_create_and_type_conflict():
    reg = MetricsRegistry()
    assert reg.counter("a") is reg.counter("a")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("a")
    reg.gauge("g").set(3.5)
    reg.histogram("h").record(0.01)
    d = reg.describe()
    assert d["g"] == {"type": "gauge", "value": 3.5}
    assert d["h"]["count"] == 1
    assert reg.names() == ["a", "g", "h"]


def test_prometheus_export_format():
    reg = MetricsRegistry()
    reg.counter("serve.ingest.blocks").inc(3)
    reg.gauge("queue.depth").set(2)
    reg.histogram("step_s").record(0.5)
    text = reg.prometheus()
    assert "# TYPE serve_ingest_blocks counter" in text
    assert "serve_ingest_blocks 3" in text
    assert "queue_depth 2" in text
    assert 'step_s_bucket{le="+Inf"} 1' in text
    assert "step_s_count 1" in text


def test_prometheus_histogram_bucket_conformance():
    # text-format conformance: _bucket series are CUMULATIVE counts per
    # upper bound, the +Inf bucket equals _count, and bounds ascend
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    for v in (1e-5, 1e-5, 0.02, 0.5, 100.0):
        h.record(v)
    lines = [ln for ln in reg.prometheus().splitlines()
             if ln.startswith("lat_bucket")]
    les, counts = [], []
    for ln in lines:
        le = ln.split('le="')[1].split('"')[0]
        les.append(float("inf") if le == "+Inf" else float(le))
        counts.append(int(ln.rsplit(" ", 1)[1]))
    assert les == sorted(les) and les[-1] == float("inf")
    assert counts == sorted(counts)            # cumulative, never drops
    assert counts[-1] == 5                     # +Inf bucket == _count
    assert "lat_count 5" in reg.prometheus()
    # the two 1e-5 samples are cumulative from the first bound >= 1e-5
    idx = next(i for i, b in enumerate(les) if b >= 1e-5)
    assert counts[idx] >= 2


def test_prometheus_label_value_escaping():
    from repro.obs.metrics import prom_escape_label, prom_sample
    assert prom_escape_label('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    line = prom_sample("m_bucket", {"le": 'x"\n\\'}, 7)
    assert line == 'm_bucket{le="x\\"\\n\\\\"} 7'
    # round-trip: an exposition-format parser un-escapes to the original
    quoted = line.split('le="')[1].rsplit('"}', 1)[0]
    unescaped = (quoted.replace("\\\\", "\x00").replace('\\"', '"')
                 .replace("\\n", "\n").replace("\x00", "\\"))
    assert unescaped == 'x"\n\\'


def test_null_registry_is_noop():
    reg = obs_metrics.NULL
    c, g, h = reg.counter("a"), reg.gauge("b"), reg.histogram("c")
    c.inc(5)
    g.set(1.0)
    h.record(0.1)
    with h.time():
        pass
    assert c.value == 0 and h.count == 0
    assert reg.describe() == {}              # nothing ever registered


# ---------------------------------------------------------------------------
# trace: nesting, bounded ring, jsonl
# ---------------------------------------------------------------------------

def test_span_nesting_and_completion_order():
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner"):
            tr.event("mark", x=1)
    evs = tr.events()
    assert [e["name"] for e in evs] == ["mark", "inner", "outer"]
    mark, inner, outer = evs
    assert outer["depth"] == 0 and outer["parent"] == 0
    assert inner["depth"] == 1 and inner["parent"] == outer["id"]
    assert mark["depth"] == 2 and mark["parent"] == inner["id"]
    assert mark["attrs"] == {"x": 1}
    assert inner["dur_s"] <= outer["dur_s"]


def test_trace_ring_bounded_and_jsonl():
    tr = Tracer(capacity=8)
    for i in range(20):
        tr.event(f"e{i}")
    evs = tr.events()
    assert len(evs) == 8                       # oldest evicted first
    assert [e["name"] for e in evs] == [f"e{i}" for i in range(12, 20)]
    lines = tr.to_jsonl(last=3).splitlines()
    assert [json.loads(ln)["name"] for ln in lines] == ["e17", "e18", "e19"]
    tr.clear()
    assert tr.events() == [] and tr.to_jsonl() == ""


def test_trace_events_carry_epoch_pid_tid():
    import os
    tr = Tracer()
    before = time.time()
    with tr.span("s"):
        tr.event("e")
    after = time.time()
    for e in tr.events():
        assert before - 1 <= e["epoch"] <= after + 1
        assert e["pid"] == os.getpid()
        assert e["tid"] == threading.get_ident()
    # the span's epoch is its START time: at or before the inner event's
    span = next(e for e in tr.events() if e["name"] == "s")
    mark = next(e for e in tr.events() if e["name"] == "e")
    assert span["epoch"] <= mark["epoch"]


def test_trace_incremental_export_since_event_id():
    tr = Tracer()
    for i in range(5):
        tr.event(f"e{i}")
    cursor = tr.events()[-1]["id"]
    assert tr.export(since_event_id=cursor) == ""   # nothing new yet
    tr.event("fresh1")
    tr.event("fresh2")
    lines = tr.export(since_event_id=cursor).splitlines()
    assert [json.loads(ln)["name"] for ln in lines] == ["fresh1",
                                                        "fresh2"]
    # default cursor 0 exports everything; last= caps from the tail
    assert len(tr.export().splitlines()) == 7
    tail = tr.export(since_event_id=0, last=2).splitlines()
    assert [json.loads(ln)["name"] for ln in tail] == ["fresh1",
                                                       "fresh2"]
    assert obs_trace.NULL.export() == ""


def test_log_emits_structured_line():
    tr = Tracer()
    out = []
    tr.log("serve.tick", _printer=out.append, step=3, rate=1.23456)
    assert out == ["[serve.tick] step=3 rate=1.235"]
    assert tr.events()[-1]["attrs"] == {"step": 3, "rate": 1.23456}


def test_span_nesting_is_per_thread():
    tr = Tracer()
    barrier = threading.Barrier(2)

    def work(name):
        with tr.span(name):
            barrier.wait(timeout=5)

    ts = [threading.Thread(target=work, args=(f"s{i}",)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # concurrent spans on different threads are both roots, not nested
    assert {e["depth"] for e in tr.events()} == {0}


def test_span_start_on_the_profilers_host_clock(tmp_path):
    """A ring span's ``start_ns`` is where the JAX profiler puts the
    span's own annotation: ``profile_start_time`` plus the host event's
    offset, within 1 ms."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tr = Tracer(annotate=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("ingest.clock"):
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    start0 = next(v for p in pd.planes for name, v in p.stats
                  if name == "profile_start_time")
    (ann,) = [ev for p in pd.planes for line in p.lines
              for ev in line.events if ev.name == "ingest.clock"]
    (ring,) = tr.events()
    assert abs(start0 + ann.start_ns - ring["start_ns"]) < 1e6
    assert ring["epoch"] == ring["start_ns"] / 1e9
    assert ann.duration_ns / 1e9 == pytest.approx(ring["dur_s"], abs=1e-3)


def test_thread_names_resolved_when_read():
    tr = Tracer()

    def work():
        with tr.span("s"):
            pass

    t = threading.Thread(target=work, name="repro-test-worker")
    t.start()
    t.join()
    tr.event("main")
    # the worker has exited; its name was kept once, not per span
    assert [e["thread"] for e in tr.events()] == [
        "repro-test-worker", threading.current_thread().name]


# ---------------------------------------------------------------------------
# IngestStats: consistent snapshots under concurrency
# ---------------------------------------------------------------------------

def test_ingest_stats_atomic_add_and_unknown_field():
    st = IngestStats()
    st.add(blocks_submitted=2, blocks_ingested=1, items_ingested=256)
    assert st.blocks_submitted == 2 and st.items_ingested == 256
    with pytest.raises(AttributeError):
        st.add(bogus_field=1)


def test_ingest_stats_snapshot_never_torn():
    """Concurrent readers must never see blocks_ingested out of sync with
    items_ingested — the cross-thread torn-read this class exists to
    prevent (each ingested block carries exactly ITEMS items)."""
    st = IngestStats()
    ITEMS = 1000
    stop = threading.Event()
    torn = []

    def reader():
        while not stop.is_set():
            d = st.describe()
            if d["items_ingested"] != d["blocks_ingested"] * ITEMS:
                torn.append(d)

    rs = [threading.Thread(target=reader) for _ in range(3)]
    for r in rs:
        r.start()
    for _ in range(20000):
        st.add(blocks_ingested=1, items_ingested=ITEMS)
    stop.set()
    for r in rs:
        r.join()
    assert not torn, f"torn stats snapshots observed: {torn[:3]}"
    assert st.describe()["items_ingested"] == 20000 * ITEMS


# ---------------------------------------------------------------------------
# health: bitwise vs the eval harness's oracle-free invariants
# ---------------------------------------------------------------------------

def test_sketch_health_matches_eval_invariants():
    from repro.eval.accuracy import oracle_free_invariants
    from repro.launch.bench_obs import HEALTH_FIELDS, compare_health

    kmaj = 16
    rt = StreamRuntime(RuntimeConfig(
        engine=EngineConfig(k=K, tenants=LANES, chunk=CHUNK,
                            buffer_depth=DEPTH, kernel="jnp"),
        shards=1))
    state = rt.init()
    for b in _blocks(rt, 8, seed=7):
        state = rt.ingest(state, host_blocks(b, rt.workers, CHUNK))
    snap = rt.snapshot(state)
    health = sketch_health(snap, k_majority=kmaj)
    report = rt.frontend().k_majority_report(snap, kmaj)
    reference = oracle_free_invariants(snap, report)
    assert compare_health(health, reference) == []
    # the gate covers every invariant field, and the stream actually
    # exercised the split (a trivially empty candidate set gates nothing)
    assert set(HEALTH_FIELDS) <= set(health)
    assert health["candidates"] > 0 and health["occupancy"] == K


def test_sketch_health_partial_summary():
    """Below occupancy k the ε bound (min_count) must report 0 — nothing
    was evicted yet, mirroring core.spacesaving.min_frequency."""
    rt = StreamRuntime(RuntimeConfig(
        engine=EngineConfig(k=K, tenants=LANES, chunk=CHUNK,
                            buffer_depth=DEPTH, kernel="jnp"),
        shards=1))
    state = rt.init()
    # 8 distinct items << k=64 counters: summary stays partially occupied
    block = np.tile(np.arange(8, dtype=np.int32), rt.workers * CHUNK // 8)
    state = rt.ingest(state, host_blocks(block, rt.workers, CHUNK))
    h = sketch_health(rt.snapshot(state), k_majority=4)
    assert h["occupancy"] < K
    assert h["min_count"] == 0 and h["saturation"] == 0.0
    assert h["epsilon_frac"] == 0.0


def test_health_monitor_refreshes_on_publish():
    reg = MetricsRegistry()
    ring = SnapshotRing(depth=4)
    rt = StreamRuntime(RuntimeConfig(
        engine=EngineConfig(k=K, tenants=LANES, chunk=CHUNK,
                            buffer_depth=DEPTH, kernel="jnp"),
        shards=1))
    state = rt.init()
    for b in _blocks(rt, 4):
        state = rt.ingest(state, host_blocks(b, rt.workers, CHUNK))
    mon = HealthMonitor(ring, reg, k_majority=8, poll_s=0.02).start()
    try:
        assert mon.latest() is None            # nothing published yet
        ring.publish(rt.snapshot(state))
        deadline = 5.0
        t0 = time.perf_counter()
        while mon.latest() is None:
            assert time.perf_counter() - t0 < deadline, "no refresh"
            time.sleep(0.005)
        h = mon.latest()
        assert h["version"] == 1
        assert reg.gauge("health.n").value == h["n"]
        assert reg.gauge("health.threshold").value == h["threshold"]
    finally:
        mon.stop()
    assert not mon.running


def test_health_gauges_skip_stale_versions():
    from repro.obs.health import HealthGauges
    reg = MetricsRegistry()
    rt = StreamRuntime(RuntimeConfig(
        engine=EngineConfig(k=K, tenants=LANES, chunk=CHUNK,
                            buffer_depth=DEPTH, kernel="jnp"),
        shards=1))
    state = rt.init()
    state = rt.ingest(state, host_blocks(_blocks(rt, 1)[0],
                                         rt.workers, CHUNK))
    old = rt.snapshot(state)                   # version 1
    state = rt.ingest(state, host_blocks(_blocks(rt, 1, seed=1)[0],
                                         rt.workers, CHUNK))
    new = rt.snapshot(state)                   # version 2
    g = HealthGauges(reg, k_majority=8)
    g.update(new)
    latest = g.update(old)                     # stale → ignored
    assert latest["version"] == 2
    assert reg.gauge("health.n").value == int(new.n)
    assert g.skipped_stale == 1
    assert reg.gauge("health.refreshes_skipped_stale").value == 1


def test_health_monitor_age_grows_when_ring_goes_quiet():
    reg = MetricsRegistry()
    ring = SnapshotRing(depth=4)
    rt = StreamRuntime(RuntimeConfig(
        engine=EngineConfig(k=K, tenants=LANES, chunk=CHUNK,
                            buffer_depth=DEPTH, kernel="jnp"),
        shards=1))
    state = rt.ingest(rt.init(), host_blocks(_blocks(rt, 1)[0],
                                             rt.workers, CHUNK))
    mon = HealthMonitor(ring, reg, k_majority=8, poll_s=0.02).start()
    try:
        assert mon.last_refresh_age_s is None  # no refresh yet
        ring.publish(rt.snapshot(state))
        t0 = time.perf_counter()
        while mon.latest() is None:
            assert time.perf_counter() - t0 < 5.0, "no refresh"
            time.sleep(0.005)
        first_age = mon.last_refresh_age_s
        assert first_age is not None and first_age < 1.0
        # the ring goes quiet: the age keeps growing and the monitor's
        # idle ticks keep the exported gauge current
        time.sleep(0.15)
        assert mon.last_refresh_age_s >= first_age + 0.1
        gauge_age = reg.gauge("health.last_refresh_age_s").value
        assert gauge_age >= 0.05               # ticked past the refresh
        assert mon.last_refresh_age_s >= gauge_age
    finally:
        mon.stop()


# ---------------------------------------------------------------------------
# tier surface: describe, read histograms, staleness, metrics=False
# ---------------------------------------------------------------------------

def test_tier_describe_exports_metrics_and_health():
    cfg = _config(health_k_majority=8)
    with ServingTier(cfg) as tier:
        rt = tier.runtime
        for b in _blocks(rt, 4):
            tier.submit(b)
        tier.drain()
        tier.frontend.estimate(np.arange(4, dtype=np.int32))
        tier.frontend.top_table(5)
        tier.frontend.k_majority_report(8)
        health = tier.health_report()
    d = tier.describe()
    assert d["metrics"]["serve.read.point_s"]["count"] == 1
    assert d["metrics"]["serve.read.top_s"]["count"] == 1
    assert d["metrics"]["serve.read.kmaj_s"]["count"] == 1
    assert d["metrics"]["serve.ingest.step_s"]["count"] == 4
    assert d["blocks_ingested"] == 4
    assert d["health"]["version"] == d["latest_version"]
    assert health["k_majority"] == 8
    # spans from the loop thread landed in the tier's tracer
    names = {e["name"] for e in tier.tracer.events()}
    assert {"ingest.step", "ingest.publish"} <= names


def test_tier_staleness_gauge_tracks_versions_behind():
    cfg = _config(publish_every=1, ring_depth=4)
    with ServingTier(cfg) as tier:
        rt = tier.runtime
        for b in _blocks(rt, 3):
            tier.submit(b)
        tier.drain()
        gauge = tier.registry.gauge("serve.read.staleness_versions")
        # a latest-snapshot read answers 0 versions behind
        tier.frontend.top_table(5)
        assert gauge.value == 0
        # a read whose snapshot was overtaken mid-flight reports the lag
        tier.frontend._observe("top", 1, time.perf_counter())
        assert gauge.value == tier.ring.latest_version - 1 > 0
    assert tier.ring.latest_version >= 3


def test_tier_metrics_off_is_noop():
    cfg = _config(metrics=False)
    with ServingTier(cfg) as tier:
        for b in _blocks(tier.runtime, 2):
            tier.submit(b)
        tier.drain()
        tier.frontend.top_table(5)
    assert tier.health is None
    assert tier.registry is obs_metrics.NULL
    assert tier.tracer is obs_trace.NULL
    d = tier.describe()
    assert d["metrics"] == {} and d["health"] is None
    assert d["blocks_ingested"] == 2           # stats still exact


# ---------------------------------------------------------------------------
# harness smoke: the CLIs' pure logic
# ---------------------------------------------------------------------------

def test_bench_obs_check_gates():
    from repro.launch.bench_obs import check_record
    record = {
        "overhead": {"ratio": 0.99},
        "health": {"tier": {"n": 1}, "reference": {"n": 1},
                   "mismatches": []},
        "drift": [{"s_true": 1.5, "s_est": 1.49, "ci_low": 1.45,
                   "ci_high": 1.55, "within_ci": True}],
        "flight": {"valid": True, "reason": "ingest_error"},
    }
    assert check_record(record, min_ratio=0.97) == []
    record["overhead"]["ratio"] = 0.9
    record["health"]["mismatches"] = ["n: health gauge 1 != invariant 2"]
    record["drift"][0]["within_ci"] = False
    record["flight"] = {"valid": False, "reason": "no dump appeared"}
    failures = check_record(record, min_ratio=0.97)
    assert len(failures) == 4
    assert any("overhead SLO" in f for f in failures)
    assert any("health inconsistency" in f for f in failures)
    assert any("drift estimator missed s=1.5" in f for f in failures)
    assert any("flight-recorder gate" in f for f in failures)
    # a record missing the sentinel phases entirely also fails
    del record["drift"], record["flight"]
    record["overhead"]["ratio"] = 0.99
    record["health"]["mismatches"] = []
    failures = check_record(record, min_ratio=0.97)
    assert any("no profiles" in f for f in failures)
    assert any("phase did not run" in f for f in failures)


def test_metrics_cli_smoke(capsys):
    from repro.launch.metrics import main
    assert main(["--blocks", "2", "--layers", "1", "--k", "64",
                 "--chunk", "128"]) == 0
    dump = json.loads(capsys.readouterr().out)
    assert "tier" in dump and "process" in dump
    assert "serve.read.top_s" in dump["tier"]["metrics"]
    assert dump["tier"]["health"]["n"] > 0
    assert dump["tier"]["blocks_ingested"] == 2


def test_metrics_cli_prometheus_and_events(capsys):
    from repro.launch.metrics import main
    assert main(["--blocks", "2", "--layers", "1", "--k", "64",
                 "--chunk", "128", "--format", "prom",
                 "--events", "4"]) == 0
    out = capsys.readouterr().out
    assert "# TYPE serve_read_top_s histogram" in out
    tail = [ln for ln in out.splitlines() if ln.startswith('{"kind"')]
    assert 1 <= len(tail) <= 4
    assert all("name" in json.loads(ln) for ln in tail)
