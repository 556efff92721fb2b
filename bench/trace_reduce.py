"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

Read with ``jax.profiler.ProfileData`` and nothing else. A device plane is
one named ``/device:TPU:<n>``; on it, the line ``XLA Modules`` holds one
event per program execution and ``XLA Ops`` one per operation. The host
planes hold the annotations the harness and the tier open
(``bench.*`` and the tier's ``ingest.*`` spans).

The slice is the ``bench.slice`` annotation the harness holds open while it
traces; its ends bound every interval below. For each device:

  busy    the union of the operation intervals inside the slice;
  idle    1 - busy / slice;
  per program: executions and device time inside the slice.

Device time is also put down to the host span that launched it: a host
event that carries a ``run_id`` (the runtime's launch of one program) is
given the innermost ``bench.*``/``ingest.*`` span open on its thread, and
each ``XLA Modules`` execution with that ``run_id`` counts for that span.
So a read's device time is what its own thread launched, whatever the
programs are called. An execution whose ``run_id`` no launch carries is
matched by name instead: a ``PjitFunction(<fn>)`` event inside a span
claims the ``jit_<fn>`` executions for it (a program launched from two
spans then counts for both). ``Reduced.attribution`` says which ties were
used.

Idle gaps are the holes in device 0's busy union, longest first, each named
by the host annotations open across it.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SLICE = "bench.slice"
HOST_SPAN = re.compile(r"^(bench\.|ingest\.)")
_SUFFIX = re.compile(r"\(\d+\)$")
_PJIT = re.compile(r"^PjitFunction\((.+)\)$")


def program_name(event_name: str) -> str:
    """``jit__ingest(123)`` → ``jit__ingest``: a program's stable name."""
    return _SUFFIX.sub("", event_name).strip()


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, disjoint (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Device:
    """One device's work inside the slice (nanoseconds)."""
    index: int
    busy_ns: float
    busy: list                    # merged busy intervals
    programs: dict                # name -> [executions, ns]
    launched: dict                # host span -> [executions, ns]


@dataclasses.dataclass
class Reduced:
    t0: float                     # slice start (ns, the trace's clock)
    t1: float                     # slice end
    devices: list
    host_spans: list              # (name, start_ns, end_ns) inside the slice
    attribution: str = "run_id"   # how launches were tied: run_id, name

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    def idle_share(self) -> float:
        """1 - busy / slice, averaged over the devices."""
        w = self.t1 - self.t0
        return sum(1.0 - d.busy_ns / w for d in self.devices) / len(
            self.devices)

    def launched_by(self, pattern: str) -> tuple[float, float]:
        """(executions, device ns) of the programs launched inside host
        spans whose name matches ``pattern``, averaged over the devices."""
        rx = re.compile(pattern)
        n = ns = 0.0
        for d in self.devices:
            for name, (c, t) in d.launched.items():
                if rx.search(name):
                    n += c
                    ns += t
        return n / len(self.devices), ns / len(self.devices)

    def host_count(self, pattern: str) -> int:
        """Host annotations matching ``pattern`` that ended in the slice."""
        rx = re.compile(pattern)
        return sum(1 for name, s, e in self.host_spans
                   if rx.search(name) and self.t0 <= e <= self.t1)

    def gaps(self, top: int = 10) -> list[tuple[str, float]]:
        """The longest holes in device 0's busy time, each named by the
        host annotations open across it."""
        d = self.devices[0]
        edges = [self.t0] + [x for iv in d.busy for x in iv] + [self.t1]
        holes = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        holes.sort(key=lambda h: h[0] - h[1])
        out = []
        for s, e in holes[:top]:
            open_ = sorted({name for name, hs, he in self.host_spans
                            if hs < e and he > s})
            label = "+".join(open_) if open_ else "no host span"
            out.append((f"{label} @{(s - self.t0) / 1e6:.3f}ms",
                        (e - s) / 1e9))
        return out

    def breakdown(self, top: int = 10) -> dict:
        """Top device programs by time (summed over the devices) and the
        longest idle gaps."""
        tot: dict = {}
        for d in self.devices:
            for name, (_, t) in d.programs.items():
                tot[name] = tot.get(name, 0.0) + t
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, t / 1e9] for n, t in ranked],
                "idle_gaps": [list(g) for g in self.gaps(top)]}


def find_trace(trace_dir) -> Path:
    found = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(found[-1])


def reduce(path, devices: int | None = None) -> Reduced:
    """Reduce one ``.xplane.pb``; ``devices`` keeps the first n device
    planes (the cell's chips)."""
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)), devices)


def reduce_profile(pd, devices: int | None = None) -> Reduced:
    """Reduce a loaded ``ProfileData``."""
    host, dev_planes = [], []
    span_of_run: dict = {}             # run_id -> launching span
    spans_of_program: dict = {}        # jit_<fn> -> launching spans
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev_planes.append((int(m.group(1)), plane))
            continue
        for line in plane.lines:
            spans, launches = [], []
            for ev in line.events:
                if HOST_SPAN.match(ev.name):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
                    continue
                fn = _PJIT.match(ev.name)
                if fn:
                    launches.append(("jit_" + fn.group(1), ev.start_ns))
                    continue
                run = _run_id(ev)
                if run is not None:
                    launches.append((run, ev.start_ns))
            host += spans
            for key, t in launches:
                inner = [(s, name) for name, s, e in spans if s <= t <= e]
                if not inner:
                    continue
                if isinstance(key, str):
                    spans_of_program.setdefault(key, set()).add(
                        max(inner)[1])
                else:
                    span_of_run[key] = max(inner)[1]
    dev_planes.sort(key=lambda p: p[0])
    if devices is not None:
        dev_planes = dev_planes[:devices]
    if not dev_planes:
        raise ValueError("the trace has no device plane (/device:TPU:n)")
    slices = [(s, e) for name, s, e in host if name == SLICE]
    lines = {i: {ln.name: list(ln.events) for ln in p.lines}
             for i, p in dev_planes}
    if slices:
        t0, t1 = slices[0]
    else:                          # no annotation: the traced device span
        evs = [e for ls in lines.values() for e in ls.get(OPS_LINE, [])]
        t0 = min(e.start_ns for e in evs)
        t1 = max(e.start_ns + e.duration_ns for e in evs)
    out, ties = [], set()
    for i, _ in dev_planes:
        ops_ev = lines[i].get(OPS_LINE, [])
        mod_ev = lines[i].get(MODULES_LINE, [])
        busy = union(clip([(e.start_ns, e.start_ns + e.duration_ns)
                           for e in (ops_ev or mod_ev)], t0, t1))
        launched = []
        for e in mod_ev:
            run = _run_id(e)
            if run in span_of_run:
                launched.append((e, span_of_run[run]))
                ties.add("run_id")
                continue
            for span in spans_of_program.get(program_name(e.name), ()):
                launched.append((e, span))
                ties.add("name")
        out.append(Device(
            index=i, busy_ns=sum(e - s for s, e in busy), busy=busy,
            programs=_tally([(e, program_name(e.name)) for e in mod_ev],
                            t0, t1),
            launched=_tally(launched, t0, t1)))
    spans = [(n, s, e) for n, s, e in host if n != SLICE
             and e > t0 and s < t1]
    return Reduced(t0=t0, t1=t1, devices=out, host_spans=spans,
                   attribution="+".join(sorted(ties)) or "none")


def _run_id(ev):
    """The ``run_id`` stat of an event (a program launch or execution)."""
    for name, value in ev.stats:
        if name == "run_id":
            return value
    return None


def _tally(named, t0, t1) -> dict:
    """[executions, ns inside the slice] per name, over (event, name)."""
    out: dict = {}
    for e, name in named:
        s, end = e.start_ns, e.start_ns + e.duration_ns
        if end <= t0 or s >= t1:
            continue
        c = out.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += min(end, t1) - max(s, t0)
    return out
