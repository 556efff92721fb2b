"""read_tail_p99_ms — the host process and its threads.

The 99th percentile over every read due in the window, from its due time
to its host-materialized answer (a failed read counts as missing): the same
samples as ``read_p50_ms``, at their tail, which a stall of the process
sets. Read from the harness's own stamps (host clock).
"""


def read(ctx):
    return ctx.measured["read_p99_ms"]
