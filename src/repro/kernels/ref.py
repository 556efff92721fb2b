"""Pure-jnp oracles for the Pallas kernels (the correctness reference).

These are also the implementations used on non-TPU backends (``impl='jnp'``):
they are fully vectorized XLA programs, so on CPU they are *faster* than
interpret-mode Pallas, while on TPU the Pallas kernels win by tiling the
match matrix through VMEM explicitly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

EMPTY = -1


def match_weights_ref(s_items: jax.Array, h_items: jax.Array,
                      h_weights: jax.Array):
    """(add_w, matched):  add_w[i] = Σ_j [s_i == h_j]·w_j,  matched[j] = ∃i.

    ``s_items`` (k,) summary item ids; ``h_items``/``h_weights`` (c,) an exact
    histogram (distinct items). EMPTY entries on either side never match.
    """
    eq = (s_items[:, None] == h_items[None, :])
    eq &= (s_items != EMPTY)[:, None]
    eq &= (h_items != EMPTY)[None, :]
    add_w = (eq * h_weights[None, :]).sum(axis=1).astype(h_weights.dtype)
    matched = eq.any(axis=0)
    return add_w, matched


def query_ref(s_items: jax.Array, s_counts: jax.Array, s_errors: jax.Array,
              queries: jax.Array):
    """(f̂, ε, monitored) for each query id against the summary."""
    eq = (s_items[:, None] == queries[None, :])
    eq &= (s_items != EMPTY)[:, None]
    monitored = eq.any(axis=0)
    f_hat = (eq * s_counts[:, None]).sum(axis=0).astype(s_counts.dtype)
    eps = (eq * s_errors[:, None]).sum(axis=0).astype(s_errors.dtype)
    return f_hat, eps, monitored


# ---------------------------------------------------------------------------
# Sorted merge-join formulations — O((k+c)·log k) instead of O(k·c)
# ---------------------------------------------------------------------------

def _lookup_sorted(s_items: jax.Array, probes: jax.Array):
    """For each probe id, the summary slot monitoring it (or a miss).

    Returns ``(slot, hit)``: ``slot[j]`` indexes ``s_items``; ``hit[j]`` is
    True iff probe j is a valid (non-EMPTY) id monitored by the summary.
    Requires valid ``s_items`` entries to be distinct (true for any summary;
    EMPTY may repeat freely — probes are >= 0 so EMPTY never matches).
    """
    k = s_items.shape[0]
    order = jnp.argsort(s_items)
    s_sorted = s_items[order]
    idx = jnp.clip(jnp.searchsorted(s_sorted, probes, side="left"), 0, k - 1)
    hit = (s_sorted[idx] == probes) & (probes != EMPTY)
    return order[idx], hit


def match_weights_sorted(s_items: jax.Array, h_items: jax.Array,
                         h_weights: jax.Array):
    """Same contract as :func:`match_weights_ref`, via sort + searchsorted.

    One k-sort plus a binary-search per histogram entry replaces the dense
    k×c match matrix: the CPU/large-k fast path used by the engine's flush
    (the dense matrix is the MXU-friendly formulation the Pallas kernel
    tiles on TPU). Bitwise-identical outputs for distinct valid s_items.
    """
    slot, hit = _lookup_sorted(s_items, h_items)
    matched = hit
    add_w = jnp.zeros(s_items.shape, h_weights.dtype).at[slot].add(
        jnp.where(hit, h_weights, 0))
    return add_w, matched


def query_sorted(s_items: jax.Array, s_counts: jax.Array, s_errors: jax.Array,
                 queries: jax.Array):
    """Same contract as :func:`query_ref`, via sort + searchsorted."""
    slot, hit = _lookup_sorted(s_items, queries)
    f_hat = jnp.where(hit, s_counts[slot], 0).astype(s_counts.dtype)
    eps = jnp.where(hit, s_errors[slot], 0).astype(s_errors.dtype)
    return f_hat, eps, hit


# ---------------------------------------------------------------------------
# Combine-match: the unified matcher behind EVERY merge (absorb-pool core)
# ---------------------------------------------------------------------------
#
# Contract (shared by ref / sorted / Pallas implementations):
#
#   (add_c, add_e, matched_s, matched_c) =
#       combine_match(s_items (k,), c_items (c,), c_counts (c,), c_errors (c,)?)
#
#   add_c[i]     = Σ_j [s_i == c_j] · c_counts[j]     (the matched f̂₂ / weight)
#   add_e[i]     = Σ_j [s_i == c_j] · c_errors[j]     (None iff c_errors is None)
#   matched_s[i] = ∃j [s_i == c_j]                    (bool, summary side)
#   matched_c[j] = ∃i [s_i == c_j]                    (bool, candidate side)
#
# EMPTY ids never match. ``c_errors=None`` is the exact-histogram case
# (zero-error candidates, COMBINE with m₂ = 0): the errors channel is skipped
# entirely so the hot ingestion path pays nothing for the unification.


def combine_match_ref(s_items: jax.Array, c_items: jax.Array,
                      c_counts: jax.Array, c_errors: jax.Array | None = None):
    """Dense k×c reference (and MXU-style formulation the Pallas kernel tiles)."""
    eq = (s_items[:, None] == c_items[None, :])
    eq &= (s_items != EMPTY)[:, None]
    eq &= (c_items != EMPTY)[None, :]
    add_c = (eq * c_counts[None, :]).sum(axis=1).astype(c_counts.dtype)
    add_e = (None if c_errors is None else
             (eq * c_errors[None, :]).sum(axis=1).astype(c_errors.dtype))
    return add_c, add_e, eq.any(axis=1), eq.any(axis=0)


def combine_match_sorted(s_items: jax.Array, c_items: jax.Array,
                         c_counts: jax.Array, c_errors: jax.Array | None = None):
    """Sorted merge-join combine-match — O((k+c)·log k) instead of O(k·c).

    One k-sort plus a binary search per candidate; this is what makes
    summary-vs-summary COMBINE cheap at large k (the dense match is
    near-quadratic in k when c = k). Bitwise-identical to
    :func:`combine_match_ref` whenever valid ids are distinct on each side
    (true for every well-formed summary and exact histogram): each summary
    slot then matches at most one candidate, so the scatter-add recovers the
    dense masked sum exactly.
    """
    slot, hit = _lookup_sorted(s_items, c_items)
    src = jnp.where(hit, c_counts, 0)
    add_c = jnp.zeros(s_items.shape, c_counts.dtype).at[slot].add(src)
    add_e = None
    if c_errors is not None:
        add_e = jnp.zeros(s_items.shape, c_errors.dtype).at[slot].add(
            jnp.where(hit, c_errors, 0))
    matched_s = jnp.zeros(s_items.shape, jnp.int32).at[slot].add(
        hit.astype(jnp.int32)) > 0
    return add_c, add_e, matched_s, hit


def combine_match_sortjoin(s_items: jax.Array, c_items: jax.Array,
                           c_counts: jax.Array,
                           c_errors: jax.Array | None = None):
    """Merge-join combine-match by two sorts, with no scatter and no gather.

    1. One stable ``lax.sort`` of summary ∪ candidates (summary first) on
       the id: a monitored id's summary entry (positions < k) then sits
       directly before its candidate entry.
    2. Each sorted entry is compared with its neighbour; a summary entry
       takes the weight (and error) of the candidate after it.
    3. A second ``lax.sort`` keyed on the position returns every result to
       slot order and candidate order (the matched flag rides in the key's
       low bit, so it costs no operand).

    O((k+c)·log(k+c)) and no k×c intermediate: the flush's matcher on the
    TPU at large k, where the dense Pallas kernel does k·c compares per
    lane. Bitwise-identical to :func:`combine_match_ref` whenever valid ids
    are distinct on each side (every summary and exact histogram).
    """
    k = s_items.shape[0]
    n = k + c_items.shape[0]
    vals = [c_counts] if c_errors is None else [c_counts, c_errors]
    vals = [jnp.concatenate([jnp.zeros((k,), v.dtype), v]) for v in vals]
    ids, pos, *vals = lax.sort(
        (jnp.concatenate([s_items, c_items]), lax.iota(jnp.int32, n), *vals),
        num_keys=1, is_stable=True)
    # distinct valid ids on each side: an equal neighbour pair is one
    # summary entry followed by its candidate
    link = (ids[:-1] == ids[1:]) & (ids[:-1] != EMPTY)
    no = jnp.zeros((1,), bool)
    hit_next = jnp.concatenate([link, no])            # the summary entry
    hit = hit_next | jnp.concatenate([no, link])      # either entry
    adds = [jnp.where(hit_next, jnp.roll(v, -1), jnp.zeros((), v.dtype))
            for v in vals]
    key, *adds = lax.sort((pos * 2 + hit.astype(jnp.int32), *adds),
                          num_keys=1)
    matched = (key & 1) == 1
    add_e = adds[1][:k] if c_errors is not None else None
    return adds[0][:k], add_e, matched[:k], matched[k:]
