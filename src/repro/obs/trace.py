"""Trace — nested span context managers over a bounded JSON-lines ring.

The qualitative half of the obs layer (DESIGN.md §12): ``span("name")``
times a region, tracks nesting per thread, and appends one event to a
fixed-capacity ring when the region closes — O(1) memory no matter how
long the tier runs, oldest events evicted first. ``event()`` records
instant (zero-duration) marks; ``log()`` additionally renders the mark as
one structured ``[name] key=value`` line, which is how the serving CLIs
emit telemetry instead of ad-hoc ``print`` formatting.

Span events record completion order (a child closes before its parent),
with ``id``/``parent``/``depth`` carrying the nesting so consumers can
rebuild the tree; ``t`` is a ``time.perf_counter()`` timestamp, so
deltas — not absolute times — are meaningful within one process. Each
event additionally carries ``start_ns``, its start on the host clock the
JAX profiler stamps its own host events with (``time.time_ns()``: a
``.xplane.pb`` holds each host event at ``profile_start_time`` plus its
offset), so a flight-recorder dump can be laid over a device trace;
``epoch`` is the same instant in seconds. ``pid``, ``tid`` and
``thread`` let exports from multiple tiers/processes be merged and
correlated with flight-recorder dumps; ``export(since_event_id=...)``
tails the ring incrementally by the monotone event id.

Recording is kept cheap for the hot loops that open spans: a closed span
rings one tuple, the process id is taken once per tracer and a thread's
name once per thread; the event dicts are built when the ring is read.

With ``annotate=True`` every span also enters a
``jax.profiler.TraceAnnotation`` of the same name, so device timelines
captured with the JAX profiler carry the host-side span names — the
pass-through degrades to a no-op when the profiler is unavailable.
"""
from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time


def fmt_event(name: str, fields: dict) -> str:
    """One structured telemetry line: ``[name] key=value ...``."""
    parts = [f"[{name}]"]
    for k, v in fields.items():
        if isinstance(v, float):
            v = f"{v:.4g}"
        parts.append(f"{k}={v}")
    return " ".join(parts)


class _Span:
    """One open span of a :class:`Tracer`; rings its record on close."""

    __slots__ = ("_tracer", "_name", "_attrs", "_ann", "_stack", "_sid",
                 "_parent", "_depth", "_tid", "_t0", "_ns0")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs or None

    def __enter__(self) -> int:
        tr = self._tracer
        stack = tr._stack()
        sid = next(tr._ids)
        self._parent = stack[-1] if stack else 0
        self._depth = len(stack)
        self._ann = None
        if tr._annotation is not None:      # device-timeline pass-through
            self._ann = tr._annotation(self._name)
            self._ann.__enter__()
        stack.append(sid)
        self._stack = stack
        self._sid = sid
        self._tid = threading.get_ident()
        self._ns0 = time.time_ns()
        self._t0 = time.perf_counter()
        return sid

    def __exit__(self, *exc) -> bool:
        dur = time.perf_counter() - self._t0
        self._stack.pop()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._tracer._append(("span", self._sid, self._parent, self._depth,
                              self._name, self._t0, dur, self._ns0,
                              self._tid, self._attrs))
        return False


def _annotation_type():
    """``jax.profiler.TraceAnnotation``, or None without the profiler."""
    try:
        from jax.profiler import TraceAnnotation
        return TraceAnnotation
    except Exception:                       # profiler unavailable
        return None


class Tracer:
    """Per-scope span/event recorder with a bounded event ring."""

    def __init__(self, capacity: int = 4096, annotate: bool = False):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self._events: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._ids = itertools.count(1)
        self._pid = os.getpid()
        self._threads: dict = {}            # tid -> name, once per thread
        self.capacity = capacity
        self.annotate = annotate
        self._annotation = _annotation_type() if annotate else None

    # -- recording -----------------------------------------------------------

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
            t = threading.current_thread()
            self._threads[t.ident] = t.name
        return st

    def _append(self, rec: tuple) -> None:
        with self._lock:
            self._events.append(rec)

    def span(self, name: str, **attrs) -> _Span:
        """Time a nested region; the event is ringed when it closes."""
        return _Span(self, name, attrs)

    def event(self, name: str, **fields) -> dict:
        """Record one instant mark (parented to the active span)."""
        stack = self._stack()
        rec = ("event", next(self._ids), stack[-1] if stack else 0,
               len(stack), name, time.perf_counter(), None, time.time_ns(),
               threading.get_ident(), fields or None)
        self._append(rec)
        return self._as_dict(rec)

    def log(self, name: str, _printer=print, **fields) -> None:
        """``event()`` + one structured stdout line — the CLI surface."""
        self.event(name, **fields)
        _printer(fmt_event(name, fields))

    # -- reading -------------------------------------------------------------

    def _as_dict(self, rec: tuple) -> dict:
        kind, sid, parent, depth, name, t, dur, ns, tid, attrs = rec
        ev = {"kind": kind, "id": sid, "parent": parent, "depth": depth,
              "name": name, "t": t}
        if kind == "span":
            ev["dur_s"] = dur
        ev.update(start_ns=ns, epoch=ns / 1e9, pid=self._pid, tid=tid,
                  thread=self._threads.get(tid, str(tid)))
        if attrs:
            ev["attrs"] = attrs
        return ev

    def _records(self, last: int | None = None) -> list:
        with self._lock:
            recs = list(self._events)
        return recs[-last:] if last is not None else recs

    def events(self, last: int | None = None) -> list:
        """Ring contents (the newest ``last``), oldest first (a
        consistent copy)."""
        return [self._as_dict(r) for r in self._records(last)]

    def to_jsonl(self, last: int | None = None) -> str:
        """The (optionally tail-truncated) ring as JSON lines."""
        return "\n".join(json.dumps(e) for e in self.events(last))

    def export(self, since_event_id: int = 0,
               last: int | None = None) -> str:
        """JSON lines for events with ``id > since_event_id`` —
        incremental tailing: feed back the max id you've seen and only
        newer events come out (ids are monotone, so eviction from the
        ring can only drop events you would have skipped anyway)."""
        recs = [r for r in self._records() if r[1] > since_event_id]
        if last is not None:
            recs = recs[-last:]
        return "\n".join(json.dumps(self._as_dict(r)) for r in recs)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return 0

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _NullTracer:
    """No-op tracer for disabled scopes (shared, allocation-free)."""

    capacity = 0
    annotate = False

    def span(self, name: str, **attrs):
        return _NULL_SPAN

    def event(self, name: str, **fields) -> dict:
        return {}

    def log(self, name: str, _printer=print, **fields) -> None:
        _printer(fmt_event(name, fields))

    def events(self, last: int | None = None) -> list:
        return []

    def to_jsonl(self, last: int | None = None) -> str:
        return ""

    def export(self, since_event_id: int = 0,
               last: int | None = None) -> str:
        return ""

    def clear(self) -> None:
        pass


DEFAULT = Tracer()
NULL = _NullTracer()


def span(name: str, **attrs):
    """A span on the process-default tracer."""
    return DEFAULT.span(name, **attrs)


def event(name: str, **fields) -> dict:
    return DEFAULT.event(name, **fields)


def log(name: str, _printer=print, **fields) -> None:
    DEFAULT.log(name, _printer=_printer, **fields)
