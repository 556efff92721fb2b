"""The benchmark's stream: the paper's Zipf generator and the replayed pool.

``fold_ids`` and ``zipf_stream`` are copies of the program's generator
(``repro.data.synthetic``), kept here so that a change to the program
cannot change what the benchmark feeds it. ``tests/bench`` pins the output
for seed 0 by checksum.

A cell's writer replays one pool of ``n_items`` ids, generated once from
the seed during set-up, in order and cyclically, as consecutive blocks:
block ``j`` holds the ids at global offsets ``[j*B, (j+1)*B)`` of the
endless sequence ``pool[0], pool[1], ..., pool[P-1], pool[0], ...``.
Generation stays out of the measured window, and the exact count of any id
in any prefix of the stream is the pool's count times the full passes plus
its count in the partial pass (:mod:`oracle`).
"""
from __future__ import annotations

import numpy as np

INT32_MAX = int(np.iinfo(np.int32).max)


def fold_ids(ids: np.ndarray, max_id: int) -> np.ndarray:
    """Map 1-based ids above ``max_id`` back into [1, max_id] (mod fold)."""
    return (ids - 1) % max_id + 1


def zipf_stream(n: int, skew: float, seed: int = 0,
                max_id: int | None = None) -> np.ndarray:
    """n Zipf(skew) item ids (int32, >= 1), the paper's generator."""
    rng = np.random.default_rng(seed)
    out = rng.zipf(skew, size=n)
    cap = max_id if max_id is not None else INT32_MAX
    return fold_ids(out, cap).astype(np.int32)


class Pool:
    """``n_items`` ids replayed cyclically as blocks of ``block`` ids."""

    def __init__(self, ids: np.ndarray, block: int):
        self.ids = np.ascontiguousarray(ids, dtype=np.int32)
        self.size = int(self.ids.size)
        self.block = int(block)
        if self.block > self.size:
            raise ValueError(f"block {block} is larger than the pool "
                             f"({self.size} ids)")
        # the pool followed by its first block: every block is then one
        # contiguous view, also where it wraps round the end of the pool
        self._ext = np.concatenate([self.ids, self.ids[:self.block]])

    def block_at(self, j: int) -> np.ndarray:
        """Block ``j`` of the endless replay (a read-only view)."""
        off = (j * self.block) % self.size
        view = self._ext[off:off + self.block]
        view.flags.writeable = False
        return view
