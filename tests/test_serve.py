"""Concurrent serving tier: ring semantics, ingest loop, frontend (§11).

Covers the serving tier's three load-bearing contracts:

  * **ring semantics** — versions are strictly monotonic, pinned reads
    never silently cross stream positions (StaleSnapshotError on
    eviction), and interleaved publish/read threads never observe a torn
    or backwards-moving snapshot;
  * **served ≡ synchronous** — a tier-ingested sketch is bitwise
    identical to ``StreamRuntime`` ingesting the same blocks
    synchronously, for every kernel impl including the fused megakernel
    (interpret mode off-TPU, so sizes here stay small);
  * **policy** — publish cadence counting, shed vs blocking admission,
    error propagation out of the loop thread, and plan-resolved
    publish_every/ring_depth knobs.

``REPRO_TEST_KERNEL`` pins the impl sweep (CI kernel-matrix legs).
"""
import asyncio
import os
import queue
import threading
import time
import types

import numpy as np
import pytest

from repro.data.synthetic import zipf_stream
from repro.engine import EngineConfig
from repro.runtime import RuntimeConfig, StreamRuntime, host_blocks
from repro.serve import (IngestLoop, ServeConfig, ServeFrontend,
                         ServingTier, SnapshotRing, StaleSnapshotError)
from repro.serve.ingest import group_sizes, group_widths

IMPLS = ((os.environ["REPRO_TEST_KERNEL"],)
         if os.environ.get("REPRO_TEST_KERNEL")
         else ("jnp", "sorted", "fused"))

K, LANES, CHUNK, DEPTH = 64, 2, 128, 2


def _runtime(kernel="jnp"):
    return StreamRuntime(RuntimeConfig(
        engine=EngineConfig(k=K, tenants=LANES, chunk=CHUNK,
                            buffer_depth=DEPTH, kernel=kernel),
        shards=1))


def _config(kernel="jnp", **kw):
    kw.setdefault("publish_every", 2)
    kw.setdefault("ring_depth", 3)
    return ServeConfig(runtime=RuntimeConfig(
        engine=EngineConfig(k=K, tenants=LANES, chunk=CHUNK,
                            buffer_depth=DEPTH, kernel=kernel),
        shards=1), **kw)


def _blocks(rt, n_blocks, seed=0):
    return [zipf_stream(rt.workers * CHUNK, 1.1, seed=seed + i,
                        max_id=10**4) for i in range(n_blocks)]


def _snap(version):
    """A minimal immutable stand-in snapshot for pure ring tests."""
    return types.SimpleNamespace(version=version, n=1000 + version)


def _summaries_equal(a, b):
    for name, x, y in zip(("items", "counts", "errors"), a.summary,
                          b.summary):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"summary.{name}")
    assert int(a.n) == int(b.n)


# ---------------------------------------------------------------------------
# SnapshotRing semantics
# ---------------------------------------------------------------------------

def test_ring_versions_strictly_monotonic():
    ring = SnapshotRing(depth=2)
    assert ring.latest() is None and ring.latest_version == 0
    ring.publish(_snap(1))
    ring.publish(_snap(2))
    assert ring.latest_version == 2
    with pytest.raises(ValueError, match="not after"):
        ring.publish(_snap(2))      # republish
    with pytest.raises(ValueError, match="not after"):
        ring.publish(_snap(1))      # time travel
    assert ring.latest_version == 2  # rejected publishes change nothing


def test_ring_pinned_get_and_eviction():
    ring = SnapshotRing(depth=2)
    for v in (1, 2, 3, 4):
        ring.publish(_snap(v))
    assert ring.get(4).version == 4
    assert ring.get(3).version == 3
    # v1/v2 were overwritten by v3/v4 in a depth-2 ring
    for stale in (1, 2):
        with pytest.raises(StaleSnapshotError):
            ring.get(stale)
    with pytest.raises(StaleSnapshotError):
        ring.get(5)                 # never published


def test_ring_wait_for():
    ring = SnapshotRing(depth=2)
    with pytest.raises(TimeoutError):
        ring.wait_for(1, timeout=0.05)
    t = threading.Timer(0.05, lambda: ring.publish(_snap(1)))
    t.start()
    assert ring.wait_for(1, timeout=5.0).version == 1
    t.join()


def test_ring_concurrent_reads_never_torn_or_backwards():
    """Readers racing a publisher: every observed snapshot is internally
    consistent (its fields travel together) and versions never move
    backwards within one reader."""
    ring = SnapshotRing(depth=4)
    stop = threading.Event()
    errors: list = []

    def read():
        seen = 0
        while not stop.is_set():
            snap = ring.latest()
            if snap is None:
                continue
            if snap.n != 1000 + snap.version:   # torn object (impossible
                errors.append(("torn", snap.version, snap.n))   # by design)
            if snap.version < seen:
                errors.append(("backwards", seen, snap.version))
            seen = snap.version
            try:
                pinned = ring.get(snap.version)
                if pinned.version != snap.version:
                    errors.append(("wrong-pin", snap.version, pinned.version))
            except StaleSnapshotError:
                pass                            # eviction race: detected, ok

    readers = [threading.Thread(target=read) for _ in range(2)]
    for t in readers:
        t.start()
    for v in range(1, 200):
        ring.publish(_snap(v))
        if v % 50 == 0:
            time.sleep(0.001)       # let starved readers run on 1 core
    stop.set()
    for t in readers:
        t.join()
    assert not errors, errors[:5]
    assert ring.latest_version == 199


# ---------------------------------------------------------------------------
# IngestLoop: served ≡ synchronous, per kernel impl
# ---------------------------------------------------------------------------

@pytest.mark.kernel_matrix
@pytest.mark.parametrize("impl", IMPLS)
def test_tier_bitwise_identical_to_sync_ingest(impl):
    rt = _runtime(impl)
    blocks = _blocks(rt, 5)

    state = rt.init()
    for b in blocks:
        state = rt.ingest(state, host_blocks(b, rt.workers, CHUNK))
    ref = rt.snapshot(state)

    with ServingTier(_config(impl), runtime=rt) as tier:
        for b in blocks:
            assert tier.submit(b)
        snap = tier.drain()
        # the drained snapshot is the ring's latest — readers see this
        # exact stream position
        assert tier.ring.latest().version == snap.version
    _summaries_equal(ref, snap)


def test_publish_cadence_counts():
    rt = _runtime()
    with ServingTier(_config(publish_every=2), runtime=rt) as tier:
        for b in _blocks(rt, 5):
            tier.submit(b)
        snap = tier.drain()
        stats = tier.stats
        # one initial publish on start + cadence publishes after blocks
        # 2 and 4 + the drain publish after block 5
        assert stats.publishes == 4
        assert stats.blocks_submitted == stats.blocks_ingested == 5
        assert stats.items_ingested == 5 * rt.workers * CHUNK
        assert stats.blocks_shed == 0
        assert tier.ring.latest_version == snap.version


def test_shed_admission_counts_drops():
    rt = _runtime()
    ring = SnapshotRing(depth=2)
    # loop NOT started: the queue can only fill
    loop = IngestLoop(rt, ring, publish_every=4, queue_depth=1,
                      admission="shed")
    assert loop.submit(np.arange(8, dtype=np.int32)) is True
    assert loop.submit(np.arange(8, dtype=np.int32)) is False
    assert loop.stats.blocks_shed == 1
    assert loop.stats.blocks_submitted == 1


def test_block_admission_backpressure_timeout():
    rt = _runtime()
    loop = IngestLoop(rt, SnapshotRing(depth=2), publish_every=4,
                      queue_depth=1, admission="block")
    assert loop.submit(np.arange(8, dtype=np.int32))
    with pytest.raises(queue.Full):
        loop.submit(np.arange(8, dtype=np.int32), timeout=0.05)


def test_loop_error_propagates_to_producers():
    rt = _runtime()
    loop = IngestLoop(rt, SnapshotRing(depth=2), publish_every=4).start()
    # a 3-d payload cannot be block-decomposed: the loop thread dies with
    # the real exception chained, and every later producer call reports it
    loop.submit(np.zeros((2, 3, 4), dtype=np.int32))
    with pytest.raises(RuntimeError, match="IngestLoop"):
        loop.drain(timeout=10)
        loop.submit(np.arange(8, dtype=np.int32))  # pragma: no cover
    with pytest.raises(RuntimeError):
        loop.submit(np.arange(8, dtype=np.int32))


def test_tier_stop_is_idempotent():
    rt = _runtime()
    tier = ServingTier(_config(), runtime=rt).start()
    tier.submit(_blocks(rt, 1)[0])
    snap = tier.stop()
    assert snap is not None and int(snap.n) == rt.workers * CHUNK
    assert tier.stop() is None      # second stop: clean no-op
    with pytest.raises(RuntimeError, match="stopped"):
        tier.submit(_blocks(rt, 1)[0])


# ---------------------------------------------------------------------------
# ServeFrontend
# ---------------------------------------------------------------------------

def test_frontend_sync_and_async_answers_match():
    rt = _runtime()
    with ServingTier(_config(), runtime=rt) as tier:
        for b in _blocks(rt, 4):
            tier.submit(b)
        snap = tier.drain()

        est = tier.frontend.estimate([1, 2, 3], min_version=snap.version)
        top = tier.frontend.top_table(3, min_version=snap.version)
        rep = tier.frontend.k_majority_report(16, min_version=snap.version)
        assert est.version == top.version == rep.version == snap.version
        assert est.n == top.n == rep.n == int(snap.n)
        assert (est.lower <= est.f_hat).all()

        async def roundtrip():
            return await asyncio.gather(
                tier.frontend.aestimate([1, 2, 3],
                                        min_version=snap.version),
                tier.frontend.atop_table(3, min_version=snap.version),
                tier.frontend.ak_majority_report(
                    16, min_version=snap.version))

        aest, atop, arep = asyncio.run(roundtrip())
        np.testing.assert_array_equal(aest.f_hat, est.f_hat)
        assert [r["item"] for r in atop.rows] == \
            [r["item"] for r in top.rows]
        np.testing.assert_array_equal(arep.guaranteed_items,
                                      rep.guaranteed_items)


def test_frontend_times_out_before_first_publish():
    rt = _runtime()
    frontend = ServeFrontend(SnapshotRing(depth=2), rt.frontend())
    with pytest.raises(TimeoutError):
        frontend.estimate([1, 2], timeout=0.05)


# ---------------------------------------------------------------------------
# Config + plan knobs
# ---------------------------------------------------------------------------

def test_serve_config_validation():
    with pytest.raises(ValueError, match="admission"):
        ServeConfig(admission="drop")
    with pytest.raises(ValueError, match="queue_depth"):
        ServeConfig(queue_depth=0)
    with pytest.raises(ValueError, match="publish_every"):
        ServeConfig(publish_every=0)
    with pytest.raises(ValueError, match="ring_depth"):
        ServeConfig(ring_depth=-1)


def test_serve_config_resolves_through_plan():
    import dataclasses

    from repro.plan import active_plan, use_plan

    plan = dataclasses.replace(active_plan(), publish_every=7, ring_depth=5)
    with use_plan(plan):
        cfg = ServeConfig()
        assert cfg.resolved_publish_every() == 7
        assert cfg.resolved_ring_depth() == 5
        # explicit knobs always beat the plan
        pinned = ServeConfig(publish_every=3, ring_depth=2)
        assert pinned.resolved_publish_every() == 3
        assert pinned.resolved_ring_depth() == 2


def test_plan_roundtrips_publish_knobs(tmp_path):
    import dataclasses
    import json

    from repro.plan import ExecutionPlan, active_plan

    plan = dataclasses.replace(active_plan(), publish_every=3, ring_depth=9)
    d = plan.to_json()
    assert d["publish_every"] == 3 and d["ring_depth"] == 9
    back = ExecutionPlan.from_json(d)
    assert back.publish_every == 3 and back.ring_depth == 9
    # plans cached before the serving tier existed load with the
    # documented static defaults
    legacy = {k: v for k, v in d.items()
              if k not in ("publish_every", "ring_depth")}
    old = ExecutionPlan.from_json(json.loads(json.dumps(legacy)))
    assert old.publish_every == 8 and old.ring_depth == 4
    with pytest.raises(ValueError):
        dataclasses.replace(plan, publish_every=0)


def test_choose_publish_cadence_from_probe_rows():
    from repro.launch.tune import _choose_publish

    rows = [{"k": 256, "publish_per_step": 0.05},
            {"k": 2048, "publish_per_step": 0.35}]
    every, depth = _choose_publish(rows, budget=0.1)
    assert every == 4               # ceil(0.35 / 0.1): the largest-k row
    assert depth == 3               # 2 + ceil(0.35 / 4)
    assert _choose_publish([]) == (8, 4)
    every, depth = _choose_publish([{"k": 64, "publish_per_step": 1e5}])
    assert every == 256 and depth == 16     # both knobs clamp


# ---------------------------------------------------------------------------
# Async pipeline (DESIGN.md §13): coalescing, lazy publishes, deep rings
# ---------------------------------------------------------------------------

@pytest.mark.kernel_matrix
@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("coalesce", (1, 4, 64, None))
@pytest.mark.parametrize("publish_every, queued", ((3, False), (4, True)))
def test_tier_coalesced_bitwise_identical(impl, coalesce, publish_every,
                                          queued):
    """Coalescing changes how many blocks share one dispatch, never the
    sketch (bitwise vs sync per-block ingest) nor the publish cadence;
    64 exceeds the queue depth, and None coalesces up to the publish
    boundary. Submitted live (publish_every 3), the drains race the
    submitter and the groups fall where they may. Queued before the loop
    starts (publish_every 4), the first wakeup drains all 7 blocks: a run
    of 4 to the publish boundary, and the 3 past it (held for the next
    wakeup) as 2 + 1, every group size there is."""
    rt = _runtime(impl)
    blocks = _blocks(rt, 7)

    state = rt.init()
    for b in blocks:
        state = rt.ingest(state, host_blocks(b, rt.workers, CHUNK))
    ref = rt.snapshot(state)

    cfg = _config(impl, publish_every=publish_every, coalesce_max=coalesce)
    tier = ServingTier(cfg, runtime=rt)
    if queued:
        for b in blocks:
            tier.submit(b)
    with tier:
        if not queued:
            for b in blocks:
                tier.submit(b)
        snap = tier.drain()
        stats = tier.stats.describe()
        groups = tier.registry.histogram(
            "serve.ingest.coalesce_blocks").describe()
    _summaries_equal(ref, snap)
    assert stats["blocks_ingested"] == 7
    # the per-block loop's publishes however the groups were cut: the
    # initial one, one at each boundary, and the drain publish
    assert stats["publishes"] == 2 + 7 // publish_every
    assert groups["sum"] == 7
    if queued:
        want = [1] * 7 if coalesce == 1 else [4, 2, 1]
        assert (groups["count"], groups["max"]) == (len(want), max(want))
    else:
        assert groups["max"] in group_widths(publish_every,
                                             coalesce or publish_every)


@pytest.mark.parametrize("every", (3, 6, 8))
@pytest.mark.parametrize("backlog", range(1, 10))
def test_group_plan_keeps_the_per_block_publishes(backlog, every):
    """For a backlog of 1 to queue_depth + 1 (8 + 1) blocks, from every
    position in a publish interval and at every width: groups are powers
    of two up to the width, none crosses a publish boundary, and the
    groups end at each position where the per-block loop publishes."""
    for coalesce in (1, 2, 3, 4, 8, 64):
        widths = group_widths(every, coalesce)
        assert widths == tuple(m for m in (1, 2, 4, 8)
                               if m <= min(coalesce, every))
        for since in range(every):
            sizes = group_sizes(backlog, since, every, coalesce)
            assert sum(sizes) == backlog
            assert set(sizes) <= set(widths)
            ends, sp, pos = set(), since, 0
            for m in sizes:
                assert sp + m <= every        # never crosses a boundary
                sp = (sp + m) % every
                pos += m
                ends.add(pos)
            per_block = {j for j in range(1, backlog + 1)
                         if (since + j) % every == 0}
            assert per_block <= ends


def test_blocks_past_a_boundary_wait_to_go_out_whole():
    """A drain that passes a publish boundary holds the blocks beyond it
    for the next wakeup, which takes them with what was queued meanwhile:
    5 queued blocks go out as 4 (then the publish) and, with 3 more queued
    while that group runs, as 1 + 3 = 4 from the boundary, not 1 and 2 + 1
    (the per-block loop's publish count; bitwise as per-block ingest)."""
    rt = _runtime()
    blocks = _blocks(rt, 8)
    state = rt.init()
    for b in blocks:
        state = rt.ingest(state, host_blocks(b, rt.workers, CHUNK))
    ref = rt.snapshot(state)

    tier = ServingTier(_config(publish_every=4, queue_depth=16), runtime=rt)
    running, release = threading.Event(), threading.Event()
    plain = rt._ingest_blocks_fn

    def first_group(state, block):     # the loop's first dispatch waits
        running.set()
        release.wait(60)
        return plain(state, block)

    rt._ingest_blocks_fn = first_group
    for b in blocks[:5]:
        tier.submit(b)
    with tier:
        assert running.wait(60)
        for b in blocks[5:]:
            tier.submit(b)
        release.set()
        snap = tier.drain(timeout=60)
        stats = tier.stats.describe()
        groups = tier.registry.histogram(
            "serve.ingest.coalesce_blocks").describe()
    _summaries_equal(ref, snap)
    # initial + after blocks 4 and 8 (the drain publishes at 8 too)
    assert stats["publishes"] == 4
    assert (groups["count"], groups["sum"], groups["min"]) == (2, 8, 4)


def test_serving_every_group_size_compiles_nothing_after_start():
    """start() compiles every group shape in both ingest programs; a
    burst served afterwards in groups of 4, 2 and 1 compiles nothing."""
    import jax.monitoring

    rt = _runtime()
    tier = ServingTier(_config(publish_every=4), runtime=rt)
    # hold the loop inside its first publish, so that the burst below is
    # queued whole before the loop's next wakeup
    held, release = threading.Event(), threading.Event()
    publish = tier.ring.publish

    def held_publish(snap):
        held.set()
        release.wait(60)
        return publish(snap)

    tier.ring.publish = held_publish
    compiles = []

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    with tier:
        assert held.wait(60)
        tier.ring.publish = publish
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        try:
            for b in _blocks(rt, 7):
                tier.submit(b)
            release.set()
            tier.drain(timeout=60)
        finally:
            jax.monitoring.unregister_event_duration_listener(on_duration)
        groups = tier.registry.histogram(
            "serve.ingest.coalesce_blocks").describe()
    assert (groups["count"], groups["sum"], groups["max"]) == (3, 7, 4)
    assert compiles == []


@pytest.mark.kernel_matrix
@pytest.mark.parametrize("impl", IMPLS)
def test_runtime_lazy_snapshot_bitwise_eager(impl):
    """A lazy publish materializes to exactly the eager snapshot, fires
    its callback once, and exposes count_floor without materializing."""
    rt = _runtime(impl)
    state = rt.init()
    for b in _blocks(rt, 3):
        state = rt.ingest(state, host_blocks(b, rt.workers, CHUNK))
    eager = rt.snapshot(state)
    n = int(np.asarray(state.n).sum())

    fired = []
    lazy = rt.snapshot(state, lazy=True, n_hint=n,
                       on_materialize=lambda: fired.append(1))
    assert lazy.materialized is False
    assert lazy.count_floor == n // K       # from n_hint, no reduction
    assert lazy.materialized is False and not fired
    _summaries_equal(eager, lazy)           # forces the reduction
    assert lazy.materialized is True and fired == [1]
    assert lazy.count_floor == eager.count_floor == n // K
    lazy.materialize()
    assert fired == [1]                     # callback fires exactly once


def test_lazy_snapshot_survives_ring_eviction():
    """The donation fence makes a lazy snapshot valid forever: hold one,
    ingest far past its ring eviction, then materialize — bitwise the
    sync prefix at the captured position."""
    rt = _runtime()
    blocks = _blocks(rt, 6)
    state = rt.init()
    for b in blocks[:2]:
        state = rt.ingest(state, host_blocks(b, rt.workers, CHUNK))
    ref = rt.snapshot(state)

    cfg = _config(publish_every=1, ring_depth=2, lazy_publish=True)
    with ServingTier(cfg, runtime=rt) as tier:
        for b in blocks[:2]:
            tier.submit(b)
        held = tier.drain()
        assert held.materialized is False
        for b in blocks[2:]:
            tier.submit(b)
        tier.drain()
        # the held version is long gone from the depth-2 ring
        with pytest.raises(StaleSnapshotError):
            tier.ring.get(held.version)
    _summaries_equal(ref, held)
    assert held.materialized is True


def test_ring_depth_64_dict_index():
    """Deep rings serve pinned reads in O(1) and evict strictly oldest-
    first: after 200 publishes into depth 64, exactly versions 137..200
    answer and everything older is stale."""
    ring = SnapshotRing(depth=64)
    for v in range(1, 201):
        ring.publish(_snap(v))
    assert ring.latest_version == 200
    for v in range(137, 201):
        assert ring.get(v).version == v
    for v in (1, 100, 136):
        with pytest.raises(StaleSnapshotError):
            ring.get(v)


def test_frontend_resolution_floor_fast_path():
    """estimate(resolution<=count_floor) answers from publish-time
    scalars — the summary is never touched, a lazy snapshot stays
    unmaterialized, and the floor-answer counter records the short
    circuit; one notch above the floor takes the real path."""
    from repro.obs.metrics import MetricsRegistry
    from repro.serve.ring import RingPublisher

    rt = _runtime()
    state = rt.init()
    for b in _blocks(rt, 4):
        state = rt.ingest(state, host_blocks(b, rt.workers, CHUNK))
    n = int(np.asarray(state.n).sum())
    floor = n // K
    assert floor >= 1

    ring = SnapshotRing(depth=2)
    RingPublisher(rt, ring).publish(state, lazy=True, n_hint=n)
    snap = ring.latest()
    reg = MetricsRegistry()
    fe = ServeFrontend(ring, rt.frontend(), registry=reg)

    est = fe.estimate([1, 2, 3], resolution=floor)
    assert snap.materialized is False
    assert est.n == n and est.version == snap.version
    np.testing.assert_array_equal(est.f_hat,
                                  np.full(3, floor, dtype=np.int64))
    assert not est.lower.any() and not est.monitored.any()
    assert reg.counter("serve.read.floor_answers").value == 1

    est2 = fe.estimate([1, 2, 3], resolution=floor + 1)
    assert snap.materialized is True
    assert (est2.f_hat >= est2.lower).all()
    assert reg.counter("serve.read.floor_answers").value == 1


def test_plan_roundtrips_pipeline_knobs():
    import dataclasses
    import json

    from repro.plan import ExecutionPlan, active_plan

    plan = dataclasses.replace(active_plan(), feed_depth=3,
                               lazy_publish=True)
    d = plan.to_json()
    assert (d["feed_depth"], d["lazy_publish"]) == (3, True)
    assert "coalesce_max" not in d
    back = ExecutionPlan.from_json(d)
    assert (back.feed_depth, back.lazy_publish) == (3, True)
    # plans cached before the async pipeline existed load with the
    # legacy behavior: double-buffer, eager publish
    legacy = {k: v for k, v in d.items()
              if k not in ("feed_depth", "lazy_publish")}
    old = ExecutionPlan.from_json(json.loads(json.dumps(legacy)))
    assert (old.feed_depth, old.lazy_publish) == (2, False)
    # a coalesce width cached by an older tuner is ignored
    assert ExecutionPlan.from_json({**d, "coalesce_max": 1}) == back
    with pytest.raises(ValueError):
        dataclasses.replace(plan, feed_depth=0)


def test_serve_config_resolves_pipeline_knobs_through_plan():
    import dataclasses

    from repro.plan import active_plan, use_plan

    plan = dataclasses.replace(active_plan(), publish_every=6,
                               feed_depth=3, lazy_publish=True)
    with use_plan(plan):
        cfg = _config(coalesce_max=None, lazy_publish=None)
        assert cfg.resolved_lazy_publish() is True
        assert cfg.runtime.resolved_feed_depth() == 3
        # explicit knobs always beat the plan — including explicit False
        pinned = _config(coalesce_max=2, lazy_publish=False)
        assert pinned.resolved_coalesce_max() == 2
        assert pinned.resolved_lazy_publish() is False
        rcfg = dataclasses.replace(cfg.runtime, feed_depth=5)
        assert rcfg.resolved_feed_depth() == 5
        # the width is not planned: groups run up to the publish
        # boundary, at the resolved publish_every
        assert cfg.resolved_coalesce_max() == 2
        assert _config(coalesce_max=None,
                       publish_every=None).resolved_coalesce_max() == 6


def test_choose_pipeline_from_probe_rows():
    from repro.launch.tune import _choose_pipeline

    rows = [
        {"op": "pipeline", "knob": "feed", "depth": 1, "block_s": 1.00},
        {"op": "pipeline", "knob": "feed", "depth": 2, "block_s": 0.80},
        {"op": "pipeline", "knob": "feed", "depth": 4, "block_s": 0.79},
        {"op": "pipeline", "knob": "publish", "step_s": 1.0,
         "eager_s": 0.2},
    ]
    fe, lazy = _choose_pipeline(rows)
    assert fe == 2      # depth 2 is within slack of depth 4 — take less
    assert lazy is True  # eager publish costs 20% of a step: defer it
    assert _choose_pipeline([]) == (2, False)
    rows[-1] = {"op": "pipeline", "knob": "publish", "step_s": 1.0,
                "eager_s": 0.01}
    assert _choose_pipeline(rows)[1] is False   # publish already cheap


# ---------------------------------------------------------------------------
# Liveness under interleaved submit/read (the tier's whole point)
# ---------------------------------------------------------------------------

def test_reads_interleave_with_ingestion():
    """Readers polling mid-stream observe monotonically growing (version,
    n) pairs and the final drain position — no reader ever blocks
    ingestion, no stale-beyond-ring answer is served."""
    rt = _runtime()
    with ServingTier(_config(publish_every=1, ring_depth=4),
                     runtime=rt) as tier:
        seen = []
        for b in _blocks(rt, 6):
            tier.submit(b)
            top = tier.frontend.top_table(2)
            seen.append((top.version, top.n))
        snap = tier.drain()
        versions = [v for v, _ in seen]
        ns = [n for _, n in seen]
        assert versions == sorted(versions)
        assert ns == sorted(ns)
        assert tier.frontend.top_table(1).version == snap.version
        # every answer's n is a real prefix position: a multiple of one
        # block, never beyond what was submitted at the time
        block_n = rt.workers * CHUNK
        for i, n in enumerate(ns):
            assert n % block_n == 0 and n <= (i + 1) * block_n


# ---------------------------------------------------------------------------
# Spans of the loop and the observer threads (what a profile ties device
# time and host time to)
# ---------------------------------------------------------------------------

def _spans(tracer):
    return [e for e in tracer.events() if e["kind"] == "span"]


def test_loop_spans_keep_each_launch_in_its_step_or_publish():
    """``ingest.stage`` is the only ``ingest.*`` child of ``ingest.step``;
    the jitted ingest runs with ``ingest.step`` innermost and the publish
    merge with ``ingest.publish`` innermost; ``ingest.wait`` never overlaps
    a step."""
    from repro.obs.trace import Tracer

    rt = _runtime()
    tr = Tracer(capacity=100_000)
    launched = []               # (program, innermost open span id)

    def spy(kind, fn):
        def call(*args, **kw):
            stack = tr._stack()
            launched.append((kind, stack[-1] if stack else 0))
            return fn(*args, **kw)
        return call

    rt._ingest_blocks_fn = spy("ingest", rt._ingest_blocks_fn)
    rt._feed_ingest_fn = spy("ingest", rt._feed_ingest_fn)
    rt._merged_fn = spy("merged", rt._merged_fn)
    blocks = _blocks(rt, 12)
    loop = IngestLoop(rt, SnapshotRing(depth=4), publish_every=3,
                      queue_depth=16, coalesce_max=2, feed_depth=2,
                      tracer=tr)
    for b in blocks[:8]:        # queued before the thread starts: one
        loop.submit(b)          # wakeup stages several groups
    loop.start()
    loop.drain(timeout=60)
    for b in blocks[8:]:
        loop.submit(b)
        time.sleep(0.01)
    loop.stop()

    spans = _spans(tr)
    by_id = {e["id"]: e for e in spans}
    assert {e["thread"] for e in spans} == {"repro-serve-ingest"}
    steps = [e for e in spans if e["name"] == "ingest.step"]
    stages = [e for e in spans if e["name"] == "ingest.stage"]
    waits = [e for e in spans if e["name"] == "ingest.wait"]
    assert steps and stages and waits
    step_ids = {e["id"] for e in steps}
    children = [e for e in spans if e["parent"] in step_ids]
    assert children and {e["name"] for e in children} == {"ingest.stage"}
    # the first staging of a wakeup runs before any step
    assert any(e["depth"] == 0 for e in stages)
    kinds = {k for k, _ in launched}
    assert kinds == {"ingest", "merged"}
    for kind, sid in launched:
        want = "ingest.step" if kind == "ingest" else "ingest.publish"
        assert by_id[sid]["name"] == want, (kind, by_id.get(sid))
    for w in waits:
        assert w["depth"] == 0
        for s in steps:
            assert (w["t"] + w["dur_s"] <= s["t"]
                    or s["t"] + s["dur_s"] <= w["t"])


def test_observer_spans_on_their_threads_and_none_with_metrics_off():
    from repro.obs import trace as obs_trace
    from repro.obs.trace import Tracer

    rt = _runtime()
    tr = Tracer(capacity=100_000)
    cfg = _config(lazy_publish=False, sample_interval_s=0.02)
    with ServingTier(cfg, runtime=rt, tracer=tr) as tier:
        assert tier.health.tracer is tr and tier.sampler.tracer is tr
        for b in _blocks(rt, 6):
            tier.submit(b)
        tier.drain()
        deadline = time.perf_counter() + 30
        while time.perf_counter() < deadline:
            where = {(e["name"], e["thread"]) for e in _spans(tr)}
            if {("ingest.observe.health", "repro-obs-health"),
                    ("ingest.observe.drift", "repro-obs-health"),
                    ("ingest.observe.tick", "repro-obs-sampler")} <= where:
                break
            time.sleep(0.02)
        else:
            pytest.fail(f"observer spans missing: {sorted(where)}")
    spans = _spans(tr)
    observe = [e for e in spans if e["name"].startswith("ingest.observe.")]
    assert {e["name"] for e in observe} == {
        "ingest.observe.health", "ingest.observe.drift",
        "ingest.observe.tick"}
    # health and drift are siblings: nothing of the tracer's nests in them
    ids = {e["id"] for e in observe}
    assert all(e["parent"] not in ids for e in spans)

    off = Tracer(capacity=100_000)
    with ServingTier(_config(metrics=False), runtime=rt,
                     tracer=off) as tier:
        for b in _blocks(rt, 4):
            tier.submit(b)
        tier.drain()
    names = {e["name"] for e in _spans(off)}
    assert "ingest.step" in names
    assert not any(n.startswith("ingest.observe.") for n in names)
    assert ServingTier(_config(metrics=False), runtime=rt).tracer \
        is obs_trace.NULL
