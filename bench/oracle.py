"""Exact counts of the replayed stream: the plain reference.

Independent of the program: numpy only. The stream is the pool of
:class:`stream.Pool` replayed cyclically, so the count of id ``x`` in the
first ``n`` ids is ``q * count(x in pool) + count(x in pool[:r])`` with
``n = q * P + r``. A stable argsort of the pool gives each id's positions
in ascending order, so the partial-pass count is one ``searchsorted``.
"""
from __future__ import annotations

import numpy as np


class Oracle:
    """Exact prefix counts over the cyclic replay of one pool."""

    def __init__(self, pool_ids: np.ndarray):
        pool = np.asarray(pool_ids)
        self.size = int(pool.size)
        self._pool = pool
        order = np.argsort(pool, kind="stable")
        self._order = order                       # positions, grouped by id
        self._sorted = pool[order]
        self.ids, starts, self.pool_counts = np.unique(
            self._sorted, return_index=True, return_counts=True)
        self._starts = starts

    def _slot(self, ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Index into ``self.ids`` per query id, and whether it occurs."""
        ids = np.asarray(ids, dtype=np.int64)
        pos = np.searchsorted(self.ids, ids)
        pos_c = np.minimum(pos, self.ids.size - 1)
        return pos_c, self.ids[pos_c] == ids

    def counts(self, ids, n: int) -> np.ndarray:
        """Exact count of each id in the first ``n`` ids of the stream."""
        ids = np.atleast_1d(np.asarray(ids))
        q, r = divmod(int(n), self.size)
        slot, present = self._slot(ids)
        out = np.zeros(ids.shape, dtype=np.int64)
        for i in np.flatnonzero(present):
            s = slot[i]
            lo = self._starts[s]
            positions = self._order[lo:lo + self.pool_counts[s]]
            out[i] = (q * int(self.pool_counts[s])
                      + int(np.searchsorted(positions, r)))
        return out

    def heavy(self, n: int, threshold: int) -> tuple[np.ndarray, np.ndarray]:
        """Ids (and counts) with at least ``threshold`` occurrences in the
        first ``n`` ids."""
        q = int(n) // self.size
        maybe = self.ids[(q + 1) * self.pool_counts >= threshold]
        c = self.counts(maybe, n)
        keep = c >= threshold
        return maybe[keep], c[keep]

    def top(self, n: int, k: int) -> tuple[np.ndarray, np.ndarray]:
        """The k ids with the largest exact counts in the first ``n`` ids
        (ties broken by id), count-descending."""
        q, r = divmod(int(n), self.size)
        # positions below r, counted per id: the partial pass
        partial = np.bincount(np.searchsorted(self.ids, self._pool[:r]),
                              minlength=self.ids.size)
        c = q * self.pool_counts.astype(np.int64) + partial
        order = np.lexsort((self.ids, -c))[:k]
        return self.ids[order], c[order]
