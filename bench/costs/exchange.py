"""The work behind ``exchange_roofline``, counted from the deployment.

One exchange is the butterfly between the shards' summaries: log2(shards)
rounds, in each of which a chip sends its summary (3·k int32: ids, counts,
errors) to its partner over ICI and receives the partner's, reads the two
from HBM and writes the COMBINE of them back. The least time of a round is
the larger of its ICI bytes over the chip's ICI bandwidth and its HBM bytes
over the chip's HBM bandwidth; the match and top-k compute is not bounded
here (the chip publishes no peak for int32 compares).
"""
import math

SUMMARY_WORDS = 3                  # ids, counts, errors
WORD_BYTES = 4                     # int32


def least_time_s(*, k: int, shards: int, peaks: dict) -> float:
    """Seconds one chip needs at least for one exchange."""
    rounds = math.log2(shards)
    summary = SUMMARY_WORDS * k * WORD_BYTES
    ici_s = rounds * 2 * summary / (peaks["ici_bits_per_s"] / 8)
    hbm_s = rounds * 3 * summary / peaks["hbm_bytes_per_s"]
    return max(ici_s, hbm_s)
