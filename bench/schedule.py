"""Open-loop schedules and the arithmetic on their stamps.

Every seed gets the same set of inter-arrival gaps, in another order: the
gaps are the quantiles of an exponential distribution (Poisson arrivals at
the given rate), shuffled by the seed. So two seeds offer the same work in
the same time, and only the order differs. The read mix is drawn the same
way: fixed shares of each operation, shuffled.

Percentiles are exact order statistics (nearest rank), never read from
histogram buckets.
"""
from __future__ import annotations

import math

import numpy as np


def poisson_due(rate_per_s: float, seconds: float,
                rng: np.random.Generator) -> np.ndarray:
    """Due times (s, from 0) of a Poisson schedule over ``seconds``.

    ``round(rate * seconds)`` arrivals whose gaps are the midpoint
    quantiles of Exp(rate), permuted by ``rng``. The first is due at 0,
    and the gaps, the last one after the final arrival included, add up
    to ``seconds``.
    """
    count = int(round(rate_per_s * seconds))
    if count <= 0:
        return np.zeros(0)
    u = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-u) / rate_per_s
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))[:-1]])


def shuffled_mix(shares: dict, count: int,
                 rng: np.random.Generator) -> list:
    """``count`` operation names in fixed shares, shuffled by ``rng``.

    Each name gets ``round(share * count)`` slots (the largest share takes
    the rounding remainder)."""
    names = sorted(shares, key=lambda s: -shares[s])
    sizes = {n: int(round(shares[n] * count)) for n in names}
    sizes[names[0]] += count - sum(sizes.values())
    ops = [n for n in names for _ in range(sizes[n])]
    return [ops[i] for i in rng.permutation(len(ops))]


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of ``values``.

    The smallest value with at least ``q`` percent of the samples at or
    below it; ``nan`` for no samples.
    """
    v = np.sort(np.asarray(values, dtype=float))
    if v.size == 0:
        return float("nan")
    rank = max(1, math.ceil(q / 100.0 * v.size))
    return float(v[rank - 1])


def freshness(due: np.ndarray, end_offsets: np.ndarray,
              stamp_t: np.ndarray, stamp_n: np.ndarray) -> np.ndarray:
    """Seconds from each block's due time to the first readable version
    that holds it.

    ``end_offsets[i]`` is the stream count once block ``i`` is in;
    ``(stamp_t, stamp_n)`` are the watcher's stamps: when a version's
    answer arrived and its item count. Stamps are taken in time order;
    a block no stamp covers gets ``inf``.
    """
    stamp_t = np.asarray(stamp_t, dtype=float)
    stamp_n = np.asarray(stamp_n, dtype=np.int64)
    # the count a reader can see never goes back; the first stamp that
    # covers an offset is found on the running maximum
    reach = np.maximum.accumulate(stamp_n) if stamp_n.size else stamp_n
    idx = np.searchsorted(reach, np.asarray(end_offsets), side="left")
    out = np.full(len(due), np.inf)
    ok = idx < reach.size
    out[ok] = stamp_t[idx[ok]] - np.asarray(due)[ok]
    return out


def count_at(t: float, stamp_t: np.ndarray, stamp_n: np.ndarray) -> int:
    """Item count of the newest readable version at time ``t`` (0 before
    the first stamp)."""
    i = int(np.searchsorted(np.asarray(stamp_t), t, side="right"))
    return int(np.max(stamp_n[:i])) if i else 0


def own_lateness(due: np.ndarray, call: np.ndarray,
                 prev_return: np.ndarray) -> np.ndarray:
    """How late the generator itself started each call: the delay past
    the due time that the previous call's return does not explain.

    A call cannot start before the previous one (the same thread)
    returned, so admission backpressure is not the generator's fault.
    """
    ready = np.maximum(np.asarray(due), np.asarray(prev_return))
    return np.maximum(0.0, np.asarray(call) - ready)
