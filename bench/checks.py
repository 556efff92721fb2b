"""The comparison that decides ``correct``: served answers against exact
counts, under the guarantees the configuration states.

Space Saving answers with ranges, not exact counts: each monitored id
carries f̂ and ε with f̂ − ε ≤ f ≤ f̂, an unmonitored id's f is at most the
summary's minimum counter, the overestimate f̂ − f is at most n/k, and every
id with f > n/k is monitored. Each reading below counts the breaches of one
guarantee, so each is an exact comparison with the limit 0.

A read is judged twice. Against the exact counts, it has to keep the
guarantees at its own n (``read_is_wrong``). Against the summary of the
version it names, it has to be exactly the answer that summary gives
(``read_differs``): the point estimates, the top-n rows and the k-majority
split, recomputed here in numpy. The first catches a wrong summary, the
second a wrong answer from a sound one.

The control is the plain reference put in the program's place with its
counts one width narrower than the configuration states (int16 for int32):
the exact top-k summary, wrapped. Its heavy counts overflow, so it reads
``underestimated`` > 0 wherever a count passes 32767.
"""
from __future__ import annotations

import numpy as np

EMPTY = -1                     # the program's free-counter id
LIMITS = {                     # every guarantee is exact: no breach allowed
    "items_lost": 0,           # |acknowledged ids - ids in the snapshot|
    "underestimated": 0,       # monitored ids with f_hat < f
    "lower_above_true": 0,     # monitored ids with f_hat - eps > f
    "over_n_per_k": 0,         # monitored ids with f_hat - f > n/k
    "error_over_n_per_k": 0,   # monitored ids with eps > n/k
    "heavy_missing": 0,        # ids with f > n/k that are not monitored
    "reads_wrong": 0,          # reads whose answer breaks a guarantee
    "reads_mismatched": 0,     # reads unlike their version's own answer
    "reads_unanswered": 0,     # reads due in the window with no answer
}


def check_summary(oracle, items, counts, errors, *, n: int, acked: int,
                  k: int) -> dict:
    """Readings of one summary of the first ``n`` stream ids."""
    items = np.asarray(items)
    live = items != EMPTY
    ids = items[live]
    f_hat = np.asarray(counts)[live].astype(np.int64)
    eps = np.asarray(errors)[live].astype(np.int64)
    f = oracle.counts(ids, n)
    heavy, _ = oracle.heavy(n, n // k + 1)
    return {
        "items_lost": abs(int(acked) - int(n)),
        "underestimated": int((f_hat < f).sum()),
        "lower_above_true": int((f_hat - eps > f).sum()),
        "over_n_per_k": int((f_hat - f > n // k).sum()),
        "error_over_n_per_k": int((eps > n // k).sum()),
        "heavy_missing": int(np.isin(heavy, ids, invert=True).sum()),
    }


def read_is_wrong(oracle, op: str, answer: dict) -> bool:
    """Whether one read's answer breaks a guarantee at its snapshot's n.

    ``answer`` holds ``n`` and, by op: ``ids``/``f_hat``/``lower``
    (point), ``items``/``counts``/``lower`` (top), or
    ``threshold``/``candidates``/``guaranteed`` (kmaj)."""
    n = int(answer["n"])
    if op == "point":
        f = oracle.counts(answer["ids"], n)
        return bool(np.any(f < answer["lower"])
                    or np.any(f > answer["f_hat"]))
    if op == "top":
        f = oracle.counts(answer["items"], n)
        return bool(np.any(f < answer["lower"])
                    or np.any(f > answer["counts"]))
    if op == "kmaj":
        heavy, _ = oracle.heavy(n, int(answer["threshold"]))
        return bool(np.isin(heavy, answer["candidates"], invert=True).any()
                    or np.isin(answer["guaranteed"], heavy,
                               invert=True).any())
    raise ValueError(f"unknown read op {op!r}")


def read_differs(op: str, answer: dict, summary: dict, *, k: int) -> bool:
    """Whether one read's answer differs from the one the summary of its
    version gives.

    ``summary`` holds that version's ``items``, ``counts``, ``errors`` and
    ``n``. A point estimate is (f̂, f̂ − ε) for a monitored id and (m, 0)
    otherwise, m the minimum counter of a full summary, else 0. A top-n
    table holds min(n, monitored) distinct monitored ids with their own
    count and lower bound, count-descending, none outside it counted
    higher (ties may fall either way). The k-majority report's candidates
    are the ids with f̂ ≥ n // k + 1 and its guaranteed ones those with
    f̂ − ε ≥ n // k + 1, as sets."""
    items = np.asarray(summary["items"]).ravel()
    counts = np.asarray(summary["counts"]).ravel().astype(np.int64)
    errors = np.asarray(summary["errors"]).ravel().astype(np.int64)
    if int(answer["n"]) != int(summary["n"]):
        return True
    live = items != EMPTY
    ids, c, e = items[live], counts[live], errors[live]
    if op == "point":
        m = int(c.min()) if live.all() and c.size else 0
        slot = {int(x): i for i, x in enumerate(ids)}
        want_f = np.array([c[slot[x]] if x in slot else m
                           for x in np.asarray(answer["ids"]).tolist()])
        want_lo = np.array([c[slot[x]] - e[slot[x]] if x in slot else 0
                            for x in np.asarray(answer["ids"]).tolist()])
        return not (np.array_equal(want_f, answer["f_hat"])
                    and np.array_equal(want_lo, answer["lower"]))
    if op == "top":
        got = np.asarray(answer["items"]).astype(np.int64)
        got_c = np.asarray(answer["counts"]).astype(np.int64)
        got_lo = np.asarray(answer["lower"]).astype(np.int64)
        if got.size != min(int(answer["asked"]), ids.size) \
                or np.unique(got).size != got.size:
            return True
        slot = {int(x): i for i, x in enumerate(ids)}
        if any(int(x) not in slot for x in got):
            return True
        at = np.array([slot[int(x)] for x in got], dtype=np.int64)
        if not (np.array_equal(c[at], got_c)
                and np.array_equal(c[at] - e[at], got_lo)):
            return True
        if np.any(np.diff(got_c) > 0):
            return True
        rest = np.delete(c, at)
        return bool(rest.size and got_c.size and rest.max() > got_c.min())
    if op == "kmaj":
        thr = int(summary["n"]) // k + 1
        if int(answer["threshold"]) != thr:
            return True
        cand = np.sort(ids[c >= thr])
        sure = np.sort(ids[c - e >= thr])
        return not (
            np.array_equal(np.sort(np.asarray(answer["candidates"])), cand)
            and np.array_equal(np.sort(np.asarray(answer["guaranteed"])),
                               sure))
    raise ValueError(f"unknown read op {op!r}")


def control_summary(oracle, *, n: int, k: int, dtype=np.int16):
    """The reference in the program's place at a narrower count width:
    the exact top-k of the first ``n`` ids, counts cast (wrapping) to
    ``dtype``, errors 0."""
    ids, c = oracle.top(n, k)
    items = np.full(k, EMPTY, np.int64)
    counts = np.zeros(k, np.int64)
    items[:ids.size] = ids
    counts[:ids.size] = c.astype(dtype)
    return items, counts, np.zeros(k, np.int64)


def verdict(readings: dict) -> bool:
    """True when every reading is within its limit."""
    return all(readings[name] <= LIMITS[name] for name in readings)
