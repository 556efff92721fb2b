"""host_us_per_block — ingest loop and host feed (``serve/ingest.py`` loop,
``runtime/feed.py`` ``DeviceStager``, ``host_blocks``).

Host microseconds per ingested block: the window's delta of the tier
registry's ``serve.ingest.step_s`` sum over its delta of
``serve.ingest.blocks``. Exact sums and counts, never bucket percentiles.
"""


def read(ctx):
    return ctx.measured["host_us_per_block"]
