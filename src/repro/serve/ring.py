"""SnapshotRing — versioned, wait-free reads of the latest QuerySnapshot.

The serving tier's one hand-off point between ingestion and queries
(DESIGN.md §11): a fixed-depth ring of immutable
:class:`~repro.service.snapshot.QuerySnapshot` objects published by the
single ingest thread and read concurrently by any number of query
threads/tasks, with no lock on either the publish or the ``latest()``
path.

Why this is safe without a reader lock:

  * every slot holds a *complete immutable object* — a frozen
    QuerySnapshot whose array leaves are jax arrays (functionally
    immutable, complete-on-read futures). A reader therefore either sees
    the previous snapshot or the new one, never a half-written hybrid:
    there is no multi-word state a reader could observe mid-update.
  * ``publish`` stores the snapshot into its ring slot and then swaps the
    ``_latest`` reference — two single-reference assignments, each atomic
    under the interpreter. Readers of ``latest()`` pay one attribute
    load.
  * the summary behind a snapshot may still be *computing* on device when
    it is published (the ingest thread dispatches the reduction
    asynchronously so publishing never stalls ingestion); jax arrays
    block the *reader* on first materialization, so a query against a
    just-published version simply waits for its own answer — the QPOPSS
    split: readers pay read latency, writers never pay for readers.

Version-pinned reads (``get(version)``) serve read-your-writes flows
through a version→snapshot index (one dict lookup — O(1) at any depth,
no modulo-slot scan); a version that has been evicted raises
:class:`StaleSnapshotError` instead of silently returning a different
stream position. Both the dict store and the lookup are single-bytecode
dict operations, atomic under the interpreter, so the read path stays
wait-free at depth 64 exactly as at depth 4.

``publish`` is single-writer by contract (the IngestLoop thread, or one
driver loop); monotonicity is enforced, not assumed. Lazy snapshots
(:class:`~repro.service.snapshot.LazyQuerySnapshot`) ring identically —
eviction drops the ring's reference, but a reader that pinned one may
still materialize it afterwards (the publisher's donation fence keeps
the captured state valid; see DESIGN.md §13).
"""
from __future__ import annotations

import collections
import threading

from repro.obs import trace as obs_trace
from repro.service.snapshot import QuerySnapshot


class StaleSnapshotError(LookupError):
    """A pinned version has been evicted from (or never entered) the ring."""


class SnapshotRing:
    """Single-writer / many-reader ring of versioned QuerySnapshots."""

    def __init__(self, depth: int = 4):
        if depth < 1:
            raise ValueError(f"ring depth must be >= 1, got {depth}")
        self.depth = depth
        # version → snapshot index + FIFO eviction order: get() is one
        # dict lookup regardless of depth, and non-contiguous versions
        # (a driver loop skipping numbers) evict oldest-first instead of
        # colliding in a modulo slot
        self._by_version: dict[int, QuerySnapshot] = {}
        self._order: collections.deque = collections.deque()
        self._latest: QuerySnapshot | None = None
        # waiters only: publish notifies under this lock, but neither
        # publish's slot/latest stores nor latest()/get() ever take it —
        # the read path stays wait-free.
        self._cond = threading.Condition()

    # -- write side (single publisher) --------------------------------------

    def publish(self, snap: QuerySnapshot) -> QuerySnapshot:
        """Make ``snap`` the latest readable version (atomic swap).

        Versions must be strictly increasing — the ring orders reports by
        version, and a republished/older version would let a reader
        time-travel backwards between two ``latest()`` calls.
        """
        prev = self._latest
        if prev is not None and snap.version <= prev.version:
            raise ValueError(
                f"publish: version {snap.version} is not after the "
                f"latest published version {prev.version} (the ring is "
                f"single-writer with strictly increasing versions)")
        self._by_version[snap.version] = snap
        self._order.append(snap.version)
        self._latest = snap
        while len(self._order) > self.depth:
            self._by_version.pop(self._order.popleft(), None)
        with self._cond:
            self._cond.notify_all()
        return snap

    # -- read side (wait-free) ----------------------------------------------

    def latest(self) -> QuerySnapshot | None:
        """The newest complete published snapshot (None before the first)."""
        return self._latest

    @property
    def latest_version(self) -> int:
        """Version of the newest published snapshot (0 before the first)."""
        snap = self._latest
        return 0 if snap is None else snap.version

    def get(self, version: int) -> QuerySnapshot:
        """The snapshot published as ``version`` — if it is still ringed.

        One atomic dict lookup (O(1) at any depth); a concurrent eviction
        between publishs yields :class:`StaleSnapshotError`, never a
        snapshot from a different stream position.
        """
        snap = self._by_version.get(version)
        if snap is None:
            raise StaleSnapshotError(
                f"version {version} is not in the ring (latest "
                f"{self.latest_version}, depth {self.depth}): it was "
                f"evicted or never published")
        return snap

    def wait_for(self, min_version: int,
                 timeout: float | None = None) -> QuerySnapshot:
        """Block until a snapshot with version >= ``min_version`` exists.

        Read-your-writes for callers that know the publish cadence (e.g.
        the bench harness waiting for the first publish). Raises
        TimeoutError on expiry.
        """
        with self._cond:
            ok = self._cond.wait_for(
                lambda: self.latest_version >= min_version, timeout)
        if not ok:
            raise TimeoutError(
                f"no snapshot reached version {min_version} within "
                f"{timeout}s (latest {self.latest_version})")
        return self._latest


class RingPublisher:
    """Binds one runtime's ``snapshot()`` to one ring — THE write surface.

    Consumers that drive their own ingestion loop (the decode loop in
    ``launch/serve.py``) publish through this instead of calling
    ``runtime.snapshot()`` ad hoc, so every published view goes through
    the same versioned ring the IngestLoop uses and readers have exactly
    one surface to consume.
    """

    def __init__(self, runtime, ring: SnapshotRing):
        self.runtime = runtime
        self.ring = ring

    def publish(self, state, *, lazy: bool = False,
                n_hint: int | None = None, on_materialize=None,
                tracer=obs_trace.NULL, on_exchange=None) -> QuerySnapshot:
        """Snapshot ``state`` (async dispatch; ingest-safe) and ring it.

        ``lazy=True`` publishes a deferred snapshot (reduction on first
        read); the caller owes the donation fence on ``state`` — see
        ``StreamRuntime.snapshot``. On a mesh the exchange between chips
        runs in ``tracer``'s ``ingest.exchange`` span and ``on_exchange``
        counts it.
        """
        return self.ring.publish(self.runtime.snapshot(
            state, lazy=lazy, n_hint=n_hint, on_materialize=on_materialize,
            tracer=tracer, on_exchange=on_exchange))
