"""publish_device_ms — publish (``runtime/runtime.py`` ``merged``,
``engine/reductions.py``, ``kernels/ss_combine.py``).

Device milliseconds per publish in the traced slice: the device time of
every program the ingest loop launched inside its ``ingest.publish`` span
(the merged reduction and what surrounds it), over the publishes that
ended in the slice. Averaged over the devices.
"""
SPANS = (r"^ingest\.publish$",)


def read(ctx):
    publishes = ctx.trace.host_count(SPANS[0])
    _, ns = ctx.trace.launched_by(SPANS[0])
    if not publishes or not ns:
        return None
    return ns / publishes / 1e6
