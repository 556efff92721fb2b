"""query_device_us_per_read — reads (``serve/frontend.py`` →
``service/frontend.py`` → ``kernels/ss_query.py``).

Device microseconds per read answered in the traced slice: the device time
of every program a reader thread launched inside its ``bench.read.*``
annotation (estimate, top-n sort, the k-majority prune, and the small
programs around them, whatever they are called), over the reads that
ended in the slice.
"""
SPANS = (r"^bench\.read\.",)


def read(ctx):
    reads = ctx.trace.host_count(SPANS[0])
    _, ns = ctx.trace.launched_by(SPANS[0])
    if not reads or not ns:
        return None
    return ns / reads / 1e3
