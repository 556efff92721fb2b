"""observer_host_ms_per_s — observers (``obs/health.py``, ``obs/drift.py``,
``obs/timeseries.py``, ``obs/alerts.py``, ``obs/recorder.py``).

Wall milliseconds per second of the traced slice spent inside the tier's
``ingest.observe.*`` spans: the health refresh (``ingest.observe.health``,
the snapshot's copy to the host included), the drift frame
(``ingest.observe.drift``) and the sampler's tick with its alerts and
flight-recorder frame (``ingest.observe.tick``), summed over their
threads, each span clipped to the slice. Wall time, so the waits for the
GIL inside a span count too: what the observers take from a host the
ingest loop shares. One reader for every split of the quantity
(``observer_host_ms_per_s.sat``, ``observer_host_ms_per_s.fresh``).
"""
import re

from bench.trace_reduce import clip

SPANS = (r"^ingest\.observe\.",)


def read(ctx):
    r = ctx.trace
    rx = re.compile(SPANS[0])
    spans = [(s, e) for name, s, e in r.host_spans if rx.search(name)]
    if not spans:
        return None
    ns = sum(e - s for s, e in clip(spans, r.t0, r.t1))
    return ns / 1e6 / ((r.t1 - r.t0) / 1e9)
