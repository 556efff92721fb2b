"""ingest_roofline — engine flush and match kernels (``engine/engine.py``,
``engine/state.py``, ``kernels/ss_match.py``).

Share (%) of the memory roofline reached by the ingest programs in the
traced slice. The work is counted from the deployment, not from the
program: the ids the tier ingested while the slice was open (the delta of
its ``serve.ingest.items`` counter across the ``bench.slice`` bounds),
each an int32 that its chip reads once from HBM (``bench/costs/ingest.py``).
The time is the device time of every program the ingest loop launched
inside its ``ingest.step`` spans, per chip.
"""
SPANS = (r"^ingest\.step$",)


def read(ctx):
    _, ns = ctx.trace.launched_by(SPANS[0])
    ids = ctx.traced["items1"] - ctx.traced["items0"]
    if not ns or not ids:
        return None
    least_s = ctx.cost("ingest").least_time_s(ids=ids / ctx.cell.chips,
                                              peaks=ctx.peaks)
    return 100.0 * least_s / (ns / 1e9)
