"""exchange_device_ms — cross-chip reduction (``core/parallel.py``,
``engine/reductions.py``, the runtime's exchange program).

Device milliseconds per exchange in the traced slice: the device time of
every program the ingest loop launched inside its ``ingest.exchange`` span
(the ``_exchange`` program: the butterfly's ``ppermute`` rounds and their
COMBINEs), over the exchange spans that ended in the slice. Averaged over
the devices. A one-chip cell has no such span and reads nothing.
"""
SPANS = (r"^ingest\.exchange$",)


def read(ctx):
    exchanges = ctx.trace.host_count(SPANS[0])
    _, ns = ctx.trace.launched_by(SPANS[0])
    if not exchanges or not ns:
        return None
    return ns / exchanges / 1e6
