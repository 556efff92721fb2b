"""admission_wait_p99_ms — admission layer (``serve/ingest.py``
``IngestLoop.submit``).

The 99th percentile, over every block due in the window, of the time from
the block's due time to the return of its ``submit``: how long a writer
waits on the bounded admission queue. Read from the harness's own stamps
(host clock), so it needs no program name.
"""


def read(ctx):
    return ctx.measured["admission_wait_p99_ms"]
