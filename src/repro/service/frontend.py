"""QueryFrontend — plans and batches read-side queries on QuerySnapshots.

The read half of the paper: ingestion answers "absorb this stream fast",
the frontend answers "which items are k-majority, and how sure are we?".
Everything here runs against an immutable :class:`QuerySnapshot` — never a
live SketchState — so serving telemetry and evaluation harnesses can query
at any rate without flushing (or even seeing) the ingest buffer.

Query surface:

  estimate(snap, q)            batched point estimates (f̂, lower, monitored)
                               through the dispatched ``kernels.ops.query``
                               (jnp / sorted / pallas — same impl choices as
                               the merge path)
  estimate_many(snap, [q...])  plan several query sets as ONE kernel call
  top(snap, n)                 n heaviest counters (guarded: n is clamped to
                               [0, k]; EMPTY slots sort last)
  top_table(snap, n)           host-side report rows, EMPTY slots dropped
  threshold(snap, c)           all items with f̂ ≥ c (host-side extraction)
  k_majority_report(snap, k')  the paper's query: candidates f̂ ≥ ⌊n/k'⌋+1
                               split into *guaranteed* (f̂ − ε ≥ ⌊n/k'⌋+1,
                               certainly k-majority) and *unconfirmed* rest

Batch planning: point-estimate batches are EMPTY-padded up to power-of-two
buckets (≥ ``min_batch``) before hitting the jitted kernel, so arbitrary
caller batch sizes compile O(log q) variants instead of one per size. The
bucket floor defaults to the active ExecutionPlan's ``query_min_batch``
(measured by ``launch.tune``: the batch size below which the query kernel
is launch-overhead-bound on this backend); likewise ``kernel='auto'``
resolves through the plan inside ``kernels.ops.query``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spacesaving import (EMPTY, Summary, bounded_estimates,
                                    prune, sort_summary)
from repro.service.snapshot import QuerySnapshot

IMPLS = ("auto", "pallas", "jnp", "sorted", "fused")
# 'fused' is the engine's megakernel impl; at the query surface
# kernels.ops.query degrades it to the megakernel's internal sorted
# matcher, so a frontend built from a fused engine is well-defined.


@functools.lru_cache(maxsize=None)
def _estimate_fn(impl: str):
    """Jitted snapshot point-estimate under one query-kernel impl.

    jax.jit caches per input shape; the frontend's bucketing keeps the
    number of live shapes logarithmic in the largest batch seen.
    """
    from repro.kernels import ops as kops

    @jax.jit
    def run(items, counts, errors, queries):
        with jax.named_scope("sketch.query.estimate"):
            s = Summary(items, counts, errors)
            f, eps, mon = kops.query(items, counts, errors, queries,
                                     impl=impl)
            return bounded_estimates(s, f, eps, mon)

    return run


_sorted_desc = jax.jit(functools.partial(
    jax.named_scope("sketch.query.top")(sort_summary), ascending=False))


@dataclasses.dataclass(frozen=True)
class FrequentItemsReport:
    """The k-majority answer, split by guarantee strength (paper §2).

    ``guaranteed`` items satisfy f̂ − ε ≥ ⌊n/k⌋+1: since f ≥ f̂ − ε they are
    *certainly* k-majority — no false positive is possible among them.
    ``unconfirmed`` items pass the f̂ threshold only; they contain every
    remaining true k-majority item (containment: f ≤ f̂) plus possible
    false positives. ``complete`` records whether the containment theorem
    applies at all (it needs at least k counters for k-majority).
    """

    version: int
    n: int
    k_majority: int
    threshold: int               # ⌊n/k⌋ + 1
    complete: bool               # snapshot.k >= k_majority
    guaranteed_items: np.ndarray
    guaranteed_counts: np.ndarray
    guaranteed_lower: np.ndarray     # f̂ − ε per guaranteed item
    unconfirmed_items: np.ndarray
    unconfirmed_counts: np.ndarray
    unconfirmed_lower: np.ndarray

    @property
    def candidate_items(self) -> np.ndarray:
        """Full candidate set (guaranteed first, then unconfirmed)."""
        return np.concatenate([self.guaranteed_items, self.unconfirmed_items])

    @property
    def candidate_counts(self) -> np.ndarray:
        return np.concatenate([self.guaranteed_counts,
                               self.unconfirmed_counts])

    def describe(self) -> dict:
        return {
            "version": self.version,
            "n": self.n,
            "k_majority": self.k_majority,
            "threshold": self.threshold,
            "complete": self.complete,
            "n_guaranteed": int(self.guaranteed_items.size),
            "n_unconfirmed": int(self.unconfirmed_items.size),
        }


class QueryFrontend:
    """Stateless query planner over QuerySnapshots, one kernel impl."""

    def __init__(self, kernel: str = "auto", *,
                 min_batch: int | None = None):
        if kernel not in IMPLS:
            raise ValueError(f"kernel {kernel!r} not in {IMPLS}")
        if min_batch is None:
            from repro.plan import active_plan
            min_batch = active_plan().query_min_batch
        if min_batch < 1:
            raise ValueError(f"min_batch must be >= 1, got {min_batch}")
        self.kernel = kernel
        self.min_batch = min_batch
        self._estimate = _estimate_fn(kernel)

    @classmethod
    def for_engine(cls, engine) -> "QueryFrontend":
        """A frontend on the same resolved kernel as a SketchEngine."""
        return cls(engine.config.resolved_kernel())

    # -- batch planning ------------------------------------------------------

    def _bucket(self, q: int) -> int:
        """Smallest power-of-two bucket (>= min_batch) holding q queries."""
        return max(self.min_batch, 1 << max(0, q - 1).bit_length())

    def plan(self, *query_sets) -> tuple[jax.Array, list[int]]:
        """Concatenate query sets into one EMPTY-padded kernel batch.

        Returns (padded (Q,) int32 batch, per-set lengths). EMPTY padding
        is query-neutral: the kernels report it unmonitored and estimate
        maps it to the m upper bound, which the unpadding drops.
        """
        sets = [jnp.atleast_1d(jnp.asarray(q, jnp.int32))
                for q in query_sets]
        sizes = [int(s.shape[0]) for s in sets]
        flat = (jnp.concatenate(sets) if sets
                else jnp.zeros((0,), jnp.int32))
        pad = self._bucket(flat.shape[0]) - flat.shape[0]
        flat = jnp.concatenate([flat, jnp.full((pad,), EMPTY, jnp.int32)])
        return flat, sizes

    # -- point estimates -----------------------------------------------------

    def estimate(self, snap: QuerySnapshot, queries):
        """(f̂, guaranteed lower bound, monitored?) per query id.

        f̂ upper-bounds the true frequency for monitored items and equals
        the summary's min counter m (an upper bound) for unmonitored ones;
        ``lower`` = f̂ − ε for monitored, 0 otherwise — so
        lower ≤ f ≤ f̂ always holds.
        """
        padded, sizes = self.plan(queries)
        s = snap.summary
        f_hat, lower, mon = self._estimate(s.items, s.counts, s.errors,
                                           padded)
        q = sizes[0]
        return f_hat[:q], lower[:q], mon[:q]

    def estimate_many(self, snap: QuerySnapshot, query_sets):
        """Plan several query sets through ONE kernel call; split results.

        Returns a list of (f̂, lower, monitored) triples, one per input
        set, in order — the batched path for callers aggregating many
        small lookups (per-request telemetry, eval sweeps).
        """
        padded, sizes = self.plan(*query_sets)
        s = snap.summary
        f_hat, lower, mon = self._estimate(s.items, s.counts, s.errors,
                                           padded)
        out, off = [], 0
        for q in sizes:
            out.append((f_hat[off:off + q], lower[off:off + q],
                        mon[off:off + q]))
            off += q
        return out

    # -- ranked / threshold reports -----------------------------------------

    def top(self, snap: QuerySnapshot, n: int = 10):
        """The n heaviest counters, count-descending; n clamped to [0, k].

        Slots beyond the snapshot's occupancy come back as (EMPTY, 0) —
        use :meth:`top_table` for a host-side view with them dropped.
        """
        n_eff = max(0, min(int(n), snap.k))
        s = _sorted_desc(snap.summary)
        return s.items[:n_eff], s.counts[:n_eff]

    def top_table(self, snap: QuerySnapshot, n: int = 10) -> list[dict]:
        """Host-side top-n rows ({item, count, lower}), EMPTY slots dropped."""
        n_eff = max(0, min(int(n), snap.k))
        s = _sorted_desc(snap.summary)
        items = np.asarray(s.items[:n_eff])
        counts = np.asarray(s.counts[:n_eff])
        errors = np.asarray(s.errors[:n_eff])
        keep = items != EMPTY
        return [{"item": int(i), "count": int(c), "lower": int(c - e)}
                for i, c, e in zip(items[keep], counts[keep], errors[keep])]

    def threshold(self, snap: QuerySnapshot, min_count: int):
        """All monitored items with f̂ ≥ min_count, count-descending."""
        items = np.asarray(snap.summary.items)
        counts = np.asarray(snap.summary.counts)
        keep = (items != EMPTY) & (counts >= int(min_count))
        order = np.argsort(-counts[keep], kind="stable")
        return items[keep][order], counts[keep][order]

    # -- the paper's query ---------------------------------------------------

    def k_majority_report(self, snap: QuerySnapshot,
                          k_majority: int) -> FrequentItemsReport:
        """Guarantee-split frequent-items report (paper's PRUNED output)."""
        if k_majority < 1:
            raise ValueError(f"k_majority must be >= 1, got {k_majority}")
        items, counts, cand, guaranteed = prune(snap.summary, snap.n,
                                                k_majority)
        items = np.asarray(items)
        counts = np.asarray(counts)
        lower = counts - np.asarray(snap.summary.errors)
        cand = np.asarray(cand)
        guaranteed = np.asarray(guaranteed)
        unconfirmed = cand & ~guaranteed
        n = int(snap.n)

        def _ranked(mask):
            order = np.argsort(-counts[mask], kind="stable")
            return (items[mask][order], counts[mask][order],
                    lower[mask][order])

        gi, gc, gl = _ranked(guaranteed)
        ui, uc, ul = _ranked(unconfirmed)
        return FrequentItemsReport(
            version=snap.version, n=n, k_majority=int(k_majority),
            threshold=n // int(k_majority) + 1,
            complete=snap.k >= int(k_majority),
            guaranteed_items=gi, guaranteed_counts=gc, guaranteed_lower=gl,
            unconfirmed_items=ui, unconfirmed_counts=uc,
            unconfirmed_lower=ul,
        )
