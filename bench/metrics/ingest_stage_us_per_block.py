"""ingest_stage_us_per_block — ingest loop and host feed (``serve/ingest.py``
loop, ``runtime/feed.py`` ``coalesce_blocks``, ``DeviceStager.stage``).

Host microseconds per block spent staging: the time inside the tier's
``ingest.stage`` spans (``np.asarray``, the canonical decomposition and
concatenation of a coalesced group, and the ``device_put`` that ships it),
clipped to the traced slice, over the blocks ingested in the slice (the
delta of the tier's ``serve.ingest.items`` counter across the slice's
bounds, over the cell's ids per block). Staging that runs before a step,
outside any ``ingest.step`` span, is counted too.
"""
import re

from bench.trace_reduce import clip

SPANS = (r"^ingest\.stage$",)


def read(ctx):
    r = ctx.trace
    rx = re.compile(SPANS[0])
    spans = [(s, e) for name, s, e in r.host_spans if rx.search(name)]
    blocks = (ctx.traced["items1"] - ctx.traced["items0"]) / ctx.cell.block
    if not spans or not blocks:
        return None
    ns = sum(e - s for s, e in clip(spans, r.t0, r.t1))
    return ns / 1e3 / blocks
