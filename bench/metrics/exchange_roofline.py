"""exchange_roofline — cross-chip reduction (``core/parallel.py``,
``engine/reductions.py``, the runtime's exchange program).

Share (%) of the roofline reached by the exchanges in the traced slice.
The work is counted from the deployment (``bench/costs/exchange.py``): per
exchange, log2(shards) rounds that each move one k-counter summary each way
over ICI and read two and write one in HBM. The time is the device time of
every program launched inside the ``ingest.exchange`` spans, per chip. The
exchange waits on its partners more than it moves bytes, so the share is
small. A one-chip cell has no such span and reads nothing.
"""
SPANS = (r"^ingest\.exchange$",)


def read(ctx):
    exchanges = ctx.trace.host_count(SPANS[0])
    _, ns = ctx.trace.launched_by(SPANS[0])
    if not exchanges or not ns:
        return None
    least_s = ctx.cost("exchange").least_time_s(
        k=int(ctx.cell.config["k"]), shards=ctx.cell.shards,
        peaks=ctx.peaks)
    return 100.0 * least_s * exchanges / (ns / 1e9)
