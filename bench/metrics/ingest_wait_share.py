"""ingest_wait_share — ingest loop and host feed (``serve/ingest.py`` loop).

Share of the traced slice the ingest loop spends waiting on an empty
admission queue: the time inside the tier's ``ingest.wait`` spans,
clipped to the slice, over the slice. At 0.8 of the knee it is the loop's
headroom; a loop that never waits is the bottleneck.
"""
import re

from bench.trace_reduce import clip

SPANS = (r"^ingest\.wait$",)


def read(ctx):
    r = ctx.trace
    rx = re.compile(SPANS[0])
    spans = [(s, e) for name, s, e in r.host_spans if rx.search(name)]
    if not spans:
        return None
    return sum(e - s for s, e in clip(spans, r.t0, r.t1)) / (r.t1 - r.t0)
