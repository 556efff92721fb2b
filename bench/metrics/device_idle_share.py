"""device_idle_share — the device.

1 - (union of the operation intervals / traced slice) on each device of
the cell, averaged over the devices (``bench/trace_reduce.py``). One
reader for every split of the quantity (``device_idle_share.sat``,
``device_idle_share.fresh``): each moves the end-to-end metric its cells
report.
"""


def read(ctx):
    return ctx.trace.idle_share()
