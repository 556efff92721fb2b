"""freshness_tail_p99_ms — the host process and its threads.

The 99th percentile, over every block due in the window, of the time from
its due time to the first watcher stamp of a version that holds it: the
same samples as ``freshness_p50_ms``, at their tail. On one chip the tail
is set by the host: a stall of the process (writer, ingest loop, watcher
and readers all stand still) backs the admission queue up, and at 0.8 of
the knee the backlog drains slowly, so a few stalls decide it. Read from
the harness's own stamps (host clock).
"""


def read(ctx):
    return ctx.measured["freshness_p99_ms"]
