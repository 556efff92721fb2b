"""ingest_step_self_us_per_block — ingest loop and host feed
(``serve/ingest.py`` loop).

Host microseconds per block inside the tier's ``ingest.step`` spans but
outside the ``ingest.stage`` spans nested in them: the jitted ingest's
dispatch, with any wait on the device's launch queue, and the step's own
bookkeeping. Both kinds of span are clipped to the traced slice; a stage
counts as nested when its interval lies inside a step's (the loop runs on
one thread, so its spans nest or are disjoint). Over the blocks ingested
in the slice, as ``ingest_stage_us_per_block`` counts them. Nothing is
read from a trace without ``ingest.stage`` spans: there the step's whole
time is not its own.
"""
import bisect
import re

from bench.trace_reduce import clip

SPANS = (r"^ingest\.step$", r"^ingest\.stage$")


def read(ctx):
    r = ctx.trace
    step_rx, stage_rx = (re.compile(p) for p in SPANS)
    steps = sorted((s, e) for name, s, e in r.host_spans
                   if step_rx.search(name))
    stages = [(s, e) for name, s, e in r.host_spans if stage_rx.search(name)]
    blocks = (ctx.traced["items1"] - ctx.traced["items0"]) / ctx.cell.block
    if not steps or not stages or not blocks:
        return None
    starts = [s for s, _ in steps]
    nested = []
    for s, e in stages:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= steps[i][1]:
            nested.append((s, e))
    ns = (sum(e - s for s, e in clip(steps, r.t0, r.t1))
          - sum(e - s for s, e in clip(nested, r.t0, r.t1)))
    return ns / 1e3 / blocks
