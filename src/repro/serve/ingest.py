"""IngestLoop — continuous StreamRuntime ingestion off a bounded queue.

The write half of the serving tier (DESIGN.md §11): one daemon thread
owns the runtime's :class:`SketchState` exclusively and drains a bounded
admission queue of host stream blocks. Each block takes the exact path
``StreamRuntime.feed`` takes — host-side canonical decomposition
(``host_blocks``), async sharded ``device_put``, jitted ingest — so a
served sketch is bitwise-identical to a batch-fed one over the same
blocks (tested in tests/test_serve.py across every kernel impl).

Throughput discipline, in order of importance (DESIGN.md §11, §13):

  * **ingestion never waits for readers.** Snapshots are published by
    dispatching the reduction *asynchronously* and swapping the ring
    pointer immediately — or, with ``lazy_publish``, not dispatching it
    at all until a reader asks; readers materialize their own answers.
  * **wakeups drain, dispatches coalesce.** Each wakeup drains every
    consecutively queued block (up to a control item), cuts them into
    runs of at most ``coalesce_max`` blocks, and ingests each group as
    ONE transfer and ONE jitted dispatch over the concatenated canonical
    decomposition — bitwise-identical to per-block ingestion (the engine
    scans chunks in order; ``coalesce_blocks``) while paying the
    Python/dispatch overhead once per group. Runs never straddle a
    publish boundary, so the publish cadence (positions AND count) is
    exactly the per-block loop's. Each run is split into descending
    powers of two (7 → 4 + 2 + 1; :func:`group_sizes`), so the group
    shapes are a fixed set, (W, m·chunk) for m in :func:`group_widths`,
    and ``start()`` compiles every one of them, in both ingest programs,
    before the loop serves: no group size compiles while serving. A
    drain that passes a publish boundary holds the blocks beyond it for
    the next wakeup, which waits for nothing: under a backlog each
    publish interval then goes out whole from its boundary, instead of
    in pieces cut where a drain happened to begin.
  * **transfers run ahead of compute.** Batches are staged through a
    :class:`~repro.runtime.feed.DeviceStager` ``feed_depth`` deep: the
    ``device_put`` of batch i+1 is issued before the ingest of batch i
    is dispatched, so host→device copies overlap compute — the
    ``feed()`` double-buffering, carried into the serving loop.
  * **the dispatch pipeline stays full.** After the first batch the loop
    threads its state through the runtime's DONATED ingest program (the
    ``feed()`` discipline — buffers aliased in place, no per-step state
    copy), and nothing on the loop path blocks on device results.
  * **publishes fence donation, not dispatch.** The one ingest that
    follows a publish runs through the NON-donating program: the
    just-published snapshot's reduction (eager) or captured state
    reference (lazy) still holds the state's buffers, and donating them
    to the next ingest would hand XLA an aliasing hazard. One extra
    state copy per publish interval is the entire cost of a snapshot on
    the write path — which is exactly what the PlanService's
    ``"publish"`` probe measures when it sizes the cadence. The same
    fence is what makes lazy snapshots valid *forever*: the captured
    state is never donated, so a reader may materialize a version long
    after the ring evicted it.

Admission control is the queue bound: ``submit`` blocks (backpressure) or
sheds (counted, reported in :class:`IngestStats`) per the configured
policy. ``drain()`` waits until everything submitted so far is ingested
and publishes a final snapshot at exactly that stream position — the
hook the bench harness's bitwise gate is built on. (Queue order is
preserved under coalescing: a drain stops at the first control item, so
a ``publish_now`` resolves after every block submitted before it and
before any block submitted after.)
"""
from __future__ import annotations

import queue
import threading
import time

import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.runtime.feed import DeviceStager, coalesce_blocks
from repro.serve.ring import RingPublisher, SnapshotRing
from repro.service.snapshot import QuerySnapshot

_BLOCK, _PUBLISH, _STOP = "block", "publish", "stop"


def group_sizes(n_blocks: int, since_publish: int, publish_every: int,
                coalesce_max: int) -> list[int]:
    """The dispatch groups of ``n_blocks`` queued blocks, in queue order.

    A run ends at ``coalesce_max`` blocks or at the next publish boundary,
    ``publish_every - since_publish`` blocks away, and is split into
    descending powers of two: every size is one of :func:`group_widths`.
    """
    sizes, sp = [], since_publish
    while n_blocks > 0:
        run = min(n_blocks, coalesce_max, publish_every - sp)
        n_blocks -= run
        sp = (sp + run) % publish_every
        while run:
            m = 1 << (run.bit_length() - 1)
            sizes.append(m)
            run -= m
    return sizes


def group_widths(publish_every: int, coalesce_max: int) -> tuple[int, ...]:
    """Every group size :func:`group_sizes` can return: the powers of two
    up to ``min(coalesce_max, publish_every)``."""
    cap = min(coalesce_max, publish_every)
    return tuple(1 << i for i in range(cap.bit_length()))


class IngestStats:
    """Host-side counters of one IngestLoop (read-only for consumers).

    Written from two threads — producers bump ``blocks_submitted`` /
    ``blocks_shed`` inside ``submit()`` while the loop thread bumps
    ``blocks_ingested`` / ``items_ingested`` / ``publishes`` — so every
    mutation and every read goes through one lock: ``describe()`` is a
    *consistent* snapshot (a reader can never observe
    ``blocks_ingested``/``items_ingested`` torn relative to each other or
    mid-update), and fields that must move together are updated in one
    ``add()`` call. The earlier dataclass mutated public fields in place,
    which let an unsynchronized reader see exactly those torn states.
    """

    FIELDS = ("blocks_submitted",   # accepted into the queue
              "blocks_shed",        # rejected by 'shed' admission
              "blocks_ingested",    # actually fed into the sketch
              "items_ingested",     # stream items across ingested blocks
              "publishes")          # snapshots published to the ring

    __slots__ = ("_lock",) + tuple("_" + f for f in FIELDS)

    def __init__(self):
        self._lock = threading.Lock()
        for f in self.FIELDS:
            setattr(self, "_" + f, 0)

    def add(self, **deltas) -> None:
        """Atomically apply one batch of counter deltas."""
        with self._lock:
            for name, d in deltas.items():
                if name not in self.FIELDS:
                    raise AttributeError(f"IngestStats has no counter "
                                         f"{name!r}")
                setattr(self, "_" + name, getattr(self, "_" + name) + d)

    def describe(self) -> dict:
        """One lock-consistent snapshot of every counter."""
        with self._lock:
            return {f: getattr(self, "_" + f) for f in self.FIELDS}

    # per-field reads share the same lock, so a single field is never
    # observed mid-update either
    @property
    def blocks_submitted(self) -> int:
        with self._lock:
            return self._blocks_submitted

    @property
    def blocks_shed(self) -> int:
        with self._lock:
            return self._blocks_shed

    @property
    def blocks_ingested(self) -> int:
        with self._lock:
            return self._blocks_ingested

    @property
    def items_ingested(self) -> int:
        with self._lock:
            return self._items_ingested

    @property
    def publishes(self) -> int:
        with self._lock:
            return self._publishes


class _Pending:
    """A publish request: resolves to the snapshot (or the loop error)."""

    def __init__(self):
        self._event = threading.Event()
        self.snapshot: QuerySnapshot | None = None

    def resolve(self, snap):
        self.snapshot = snap
        self._event.set()

    def wait(self, timeout=None) -> QuerySnapshot | None:
        if not self._event.wait(timeout):
            raise TimeoutError("publish request not served in time")
        return self.snapshot


class IngestLoop:
    """Single consumer thread: queue → decompose → ingest → publish."""

    def __init__(self, runtime, ring: SnapshotRing, *,
                 publish_every: int, queue_depth: int = 8,
                 admission: str = "block", coalesce_max: int = 1,
                 feed_depth: int = 2, lazy_publish: bool = False,
                 state=None, registry=None, tracer=None, on_error=None):
        if publish_every < 1:
            raise ValueError(
                f"publish_every must be >= 1, got {publish_every}")
        if admission not in ("block", "shed"):
            raise ValueError(f"admission {admission!r} not in "
                             f"('block', 'shed')")
        if coalesce_max < 1:
            raise ValueError(
                f"coalesce_max must be >= 1, got {coalesce_max}")
        if feed_depth < 1:
            raise ValueError(f"feed_depth must be >= 1, got {feed_depth}")
        self.runtime = runtime
        self.ring = ring
        self.publish_every = publish_every
        self.admission = admission
        self.coalesce_max = coalesce_max
        self.feed_depth = feed_depth
        self.lazy_publish = lazy_publish
        self.stats = IngestStats()
        # instruments are created once here; record() on the loop path is
        # then O(1) with no name lookups (DESIGN.md §12 overhead budget)
        self.registry = (obs_metrics.DEFAULT if registry is None
                         else registry)
        self.tracer = obs_trace.DEFAULT if tracer is None else tracer
        reg = self.registry
        self._m_queue_depth = reg.gauge("serve.ingest.queue_depth")
        self._m_step = reg.histogram("serve.ingest.step_s")
        self._m_blocks = reg.counter("serve.ingest.blocks")
        self._m_items = reg.counter("serve.ingest.items")
        self._m_shed = reg.counter("serve.ingest.shed")
        # pipeline observability (DESIGN.md §13): actual coalesce batch
        # sizes, and how many lazy publishes a reader ever forced
        self._m_coalesce = reg.histogram("serve.ingest.coalesce_blocks")
        self._m_deferred = reg.counter("serve.publish.deferred")
        self._m_materialized = reg.counter("serve.publish.materialized")
        # launches of the exchange between chips (0 on one shard)
        self._m_exchanges = reg.counter("serve.publish.exchanges")
        # invoked (once, from the loop thread) with the captured
        # exception — the flight recorder's ingest-error dump trigger
        self.on_error = on_error
        self._publisher = RingPublisher(runtime, ring)
        self._queue: queue.Queue = queue.Queue(maxsize=queue_depth)
        self._state = state if state is not None else runtime.init()
        self._error: BaseException | None = None
        self._closed = False        # no further submissions accepted
        self._thread = threading.Thread(
            target=self._run, name="repro-serve-ingest", daemon=True)

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "IngestLoop":
        self.runtime.compile_ingest(
            group_widths(self.publish_every, self.coalesce_max))
        self._thread.start()
        return self

    def __enter__(self) -> "IngestLoop":
        return self.start()

    def __exit__(self, exc_type, *_):
        self.stop(drain=exc_type is None)

    @property
    def running(self) -> bool:
        return self._thread.is_alive()

    def _check_error(self):
        if self._error is not None:
            raise RuntimeError(
                "IngestLoop failed; no further blocks will be ingested"
            ) from self._error

    # -- producer side -------------------------------------------------------

    def submit(self, block, *, timeout: float | None = None) -> bool:
        """Enqueue one (N,) host stream block; returns False iff shed.

        ``'block'`` admission waits for queue space (raises ``queue.Full``
        only if ``timeout`` expires — bounded backpressure); ``'shed'``
        drops immediately on a full queue and counts the loss.
        """
        self._check_error()
        if self._closed:
            raise RuntimeError("IngestLoop is stopped; cannot submit")
        if self.admission == "shed":
            try:
                self._queue.put_nowait((_BLOCK, block))
            except queue.Full:
                self.stats.add(blocks_shed=1)
                self._m_shed.inc()
                return False
        else:
            self._queue.put((_BLOCK, block), timeout=timeout)
        self.stats.add(blocks_submitted=1)
        self._m_queue_depth.set(self._queue.qsize())
        return True

    def publish_now(self, timeout: float | None = None) -> QuerySnapshot:
        """Queue-ordered snapshot publish: after everything submitted so
        far, before anything submitted later. Blocks until served."""
        self._check_error()
        req = _Pending()
        self._queue.put((_PUBLISH, req))
        remaining = timeout
        while True:                 # poll so a dead loop thread can't
            try:                    # strand the waiter forever
                snap = req.wait(0.1 if remaining is None
                                else min(0.1, remaining))
                break
            except TimeoutError:
                self._check_error()
                if not self.running:
                    raise RuntimeError(
                        "IngestLoop thread exited before serving the "
                        "publish request") from None
                if remaining is not None:
                    remaining -= 0.1
                    if remaining <= 0:
                        raise
        self._check_error()
        return snap

    def drain(self, timeout: float | None = None) -> QuerySnapshot:
        """Ingest everything already queued, then publish that position."""
        return self.publish_now(timeout)

    def sync(self) -> None:
        """Block until the device work behind every dispatched ingest has
        completed — a *measurement* barrier, not a serving primitive.

        ``drain()`` resolves when the loop has dispatched everything
        queued; the dispatches themselves stay asynchronous, and with
        coalescing + lazy publishes a whole stream can fit the backend's
        in-flight window — a timer stopped at ``drain()`` would then
        measure enqueue, not compute. The bench harness calls this inside
        its timed region so updates/sec means sustained ingest. Readers
        never need it: they block on materializing their own answers.
        """
        import jax

        jax.block_until_ready(self._state)

    def stop(self, *, drain: bool = True,
             timeout: float | None = None) -> QuerySnapshot | None:
        """Stop the loop; with ``drain`` (default) finish queued work and
        publish the final position first. Idempotent."""
        snap = None
        if self._closed:
            self._thread.join(timeout)
            return None
        if drain and self.running and self._error is None:
            snap = self.drain(timeout)
        self._closed = True
        if self.running:
            self._queue.put((_STOP, None))
        self._thread.join(timeout)
        self._check_error()
        return snap

    # -- consumer side (the loop thread) ------------------------------------

    def _run(self):
        rt = self.runtime
        chunk = rt.config.engine.chunk
        workers = rt.workers
        ingest_plain = rt._ingest_blocks_fn
        ingest_donated = rt._feed_ingest_fn
        stager = DeviceStager(sharding=rt.block_sharding(),
                              depth=self.feed_depth)
        # spans of the loop thread (DESIGN.md §12): ``ingest.wait`` while
        # the queue is empty, ``ingest.stage`` per staged group. Each
        # jitted dispatch stays directly inside ``ingest.step`` or
        # ``ingest.publish``, so a profile ties its device time there
        span = self.tracer.span
        # first call must not donate the caller-provided initial state
        donate_ok = False
        since_publish = 0
        carry = []      # drained blocks held back past a publish boundary
        try:
            # version 0-of-this-loop: readers attached before the first
            # block always find a complete (possibly empty) snapshot
            self._publish()
            while True:
                if carry:
                    # held-back blocks go out now, with whatever was queued
                    # meanwhile: nothing to wait for
                    payloads, carry, ctl = carry, [], None
                else:
                    with span("ingest.wait"):
                        item = self._queue.get()
                    if item[0] != _BLOCK:
                        kind, payload = item
                        if kind == _STOP:
                            break
                        since_publish = 0
                        donate_ok = False
                        payload.resolve(self._publish())
                        continue
                    payloads, ctl = [item[1]], None

                # drain every consecutively queued block; a control item
                # ends the drain (blocks batched here all PRECEDE it in
                # queue order, so ingest-then-resolve keeps publish_now's
                # "after everything submitted so far" contract)
                while ctl is None:
                    try:
                        nxt = self._queue.get_nowait()
                    except queue.Empty:
                        break
                    if nxt[0] == _BLOCK:
                        payloads.append(nxt[1])
                    else:
                        ctl = nxt

                # a drain that passes a publish boundary holds the blocks
                # beyond it for the next wakeup, which takes them with what
                # was queued meanwhile: under a backlog each interval then
                # goes out whole from its boundary, in the fewest groups,
                # wherever the drains happen to start
                n = len(payloads)
                tail = (since_publish + n) % self.publish_every
                if ctl is None and 0 < tail < n:
                    payloads, carry = payloads[:n - tail], payloads[n - tail:]

                # pre-plan coalesce groups: capped at coalesce_max AND at
                # the distance to the next publish boundary, so publish
                # positions and counts are identical to the per-block loop
                groups, i = [], 0
                for m in group_sizes(len(payloads), since_publish,
                                     self.publish_every, self.coalesce_max):
                    groups.append(payloads[i:i + m])
                    i += m

                # stage ahead (async device_put), then dispatch each
                # group's single coalesced ingest; take() → top_up() →
                # dispatch keeps feed_depth transfers in flight while the
                # previous group's compute runs
                gi = 0

                def top_up():
                    nonlocal gi
                    while gi < len(groups) and stager.room:
                        g = groups[gi]
                        with span("ingest.stage"):
                            arrays = [np.asarray(p) for p in g]
                            block = coalesce_blocks(arrays, workers, chunk)
                            items = sum(int(a.size) for a in arrays)
                            stager.stage(block, (len(g), items))
                        gi += 1

                top_up()
                while len(stager):
                    t0 = time.perf_counter()
                    with span("ingest.step"):
                        dev, (nb, items) = stager.take()
                        top_up()
                        if dev.shape[-1]:
                            fn = (ingest_donated if donate_ok
                                  else ingest_plain)
                            self._state = fn(self._state, dev)
                            donate_ok = True
                            self.stats.add(blocks_ingested=nb,
                                           items_ingested=items)
                            self._m_items.inc(items)
                        else:
                            self.stats.add(blocks_ingested=nb)
                    self._m_blocks.inc(nb)
                    self._m_coalesce.record(nb)
                    self._m_step.record(time.perf_counter() - t0)
                    self._m_queue_depth.set(self._queue.qsize())
                    since_publish += nb
                    if since_publish >= self.publish_every:
                        since_publish = 0
                        # the published reduction (or a lazy snapshot's
                        # captured reference) reads these state buffers;
                        # the next ingest must not donate them (see
                        # module docstring) — dispatch stays async
                        donate_ok = False
                        self._publish()

                if ctl is not None:
                    kind, payload = ctl
                    if kind == _STOP:
                        break
                    since_publish = 0
                    donate_ok = False
                    payload.resolve(self._publish())
        except BaseException as e:           # pragma: no cover - rethreaded
            self._error = e
            # unblock any publish waiters; they re-raise via _check_error
            try:
                while True:
                    kind, payload = self._queue.get_nowait()
                    if kind == _PUBLISH:
                        payload.resolve(None)
            except queue.Empty:
                pass
            self.tracer.event("ingest.error", type=type(e).__name__,
                              message=str(e))
            if self.on_error is not None:
                try:
                    self.on_error(e)        # flight-recorder dump
                except Exception:           # a broken recorder must not
                    pass                    # mask the original error

    def _publish(self) -> QuerySnapshot:
        # the span covers the (async or deferred) dispatch + ring swap:
        # the write path's entire snapshot cost (readers pay
        # materialization). Lazy publishes capture the state reference +
        # the writer's own item count (the count_floor ε filter) and ring
        # immediately; the materialized counter tells the bench how many
        # versions a reader ever actually forced. On a mesh the exchange
        # program launches in an ``ingest.exchange`` span nested here.
        lazy = self.lazy_publish
        with self.tracer.span("ingest.publish"):
            snap = self._publisher.publish(
                self._state, lazy=lazy,
                n_hint=self.stats.items_ingested if lazy else None,
                on_materialize=self._m_materialized.inc if lazy else None,
                tracer=self.tracer, on_exchange=self._m_exchanges.inc)
        if lazy:
            self._m_deferred.inc()
        self.stats.add(publishes=1)
        return snap
