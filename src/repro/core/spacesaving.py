"""Space Saving summaries in JAX — TPU-native formulation.

The paper's sequential Space Saving (Metwally et al.) keeps ``k`` counters in
a hash table + min-ordered structure. On TPU we keep the summary as three
fixed-shape arrays and replace pointer chasing with dense vector ops:

  items  (k,) int32   monitored item ids, ``EMPTY`` (= -1) marks a free slot
  counts (k,) int32   estimated frequencies  f̂
  errors (k,) int32   per-counter overestimation bound ε (Metwally's ε_i)

Invariants (tested in tests/test_properties.py):
  * overestimation:  f(x) ≤ f̂(x)          for every monitored x
  * bounded error:   f̂(x) − f(x) ≤ ε(x) ≤ m   (m = min counter of a full summary)
  * containment:     every x with f(x) > n/k is monitored

Two update paths are provided:

  * :func:`update_scalar` / :func:`spacesaving_scan` — the literal sequential
    algorithm as a ``lax.scan`` (the oracle; also the "Intel-Phi-style" scalar
    formulation the paper shows cannot exploit wide-vector units).
  * :func:`update_chunk` / :func:`spacesaving_chunked` — the TPU-native path:
    sort a chunk, reduce it to an exact histogram, and merge the histogram
    into the summary in one vectorized step (sort + segment-sum + match
    matrix + top_k). This is the hardware adaptation described in DESIGN.md §2.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

EMPTY = -1  # sentinel item id; real item ids must be >= 0


class Summary(NamedTuple):
    """A Space Saving stream summary with ``k`` counters."""

    items: jax.Array   # (k,) int32
    counts: jax.Array  # (k,) count_dtype
    errors: jax.Array  # (k,) count_dtype

    @property
    def k(self) -> int:
        return self.items.shape[-1]


def init_summary(k: int, count_dtype=jnp.int32) -> Summary:
    """An empty summary with ``k`` free counters (the COMBINE identity)."""
    return Summary(
        items=jnp.full((k,), EMPTY, dtype=jnp.int32),
        counts=jnp.zeros((k,), dtype=count_dtype),
        errors=jnp.zeros((k,), dtype=count_dtype),
    )


def min_frequency(s: Summary) -> jax.Array:
    """m = min counter value of a *full* summary, else 0.

    m upper-bounds the count of any item NOT monitored by ``s``. When the
    summary still has free counters, no item was ever evicted, so the bound
    for unmonitored items is exactly 0.
    """
    full = jnp.all(s.items != EMPTY)
    return jnp.where(full, jnp.min(s.counts), jnp.zeros((), s.counts.dtype))


# ---------------------------------------------------------------------------
# Sequential oracle (scalar formulation — one stream element per step)
# ---------------------------------------------------------------------------

def update_scalar(s: Summary, x: jax.Array) -> Summary:
    """One classical Space Saving step for a single item ``x``.

    if x monitored:  f̂(x) += 1
    else:            evict the min counter j:  item←x, f̂←m+1, ε←m
    (a free slot is a counter with count 0, so argmin handles both cases)
    """
    eq = s.items == x
    found = eq.any()
    j_min = jnp.argmin(s.counts)
    j = jnp.where(found, jnp.argmax(eq), j_min)
    m = s.counts[j_min]
    one = jnp.ones((), s.counts.dtype)
    new_count = jnp.where(found, s.counts[j] + one, m + one)
    new_error = jnp.where(found, s.errors[j], m)
    return Summary(
        items=s.items.at[j].set(x.astype(s.items.dtype)),
        counts=s.counts.at[j].set(new_count),
        errors=s.errors.at[j].set(new_error),
    )


@functools.partial(jax.jit, static_argnames=())
def spacesaving_scan(s: Summary, stream: jax.Array) -> Summary:
    """Sequential Space Saving over ``stream`` (oracle; O(n·k) vector work).

    Elements equal to ``EMPTY`` are skipped (padding).
    """
    def body(carry: Summary, x):
        upd = update_scalar(carry, x)
        keep = x == EMPTY
        out = jax.tree.map(lambda a, b: jnp.where(keep, a, b), carry, upd)
        return out, None

    out, _ = lax.scan(body, s, stream)
    return out


# ---------------------------------------------------------------------------
# Chunked TPU-native update
# ---------------------------------------------------------------------------

def chunk_histogram(chunk: jax.Array, count_dtype=jnp.int32):
    """Exact histogram of one chunk via sort + segment reduction.

    Returns ``(items, weights)`` of the same length C as the chunk; the first
    ``n_distinct`` positions hold distinct items with their exact counts, the
    rest are (EMPTY, 0) padding. ``EMPTY`` elements in the chunk (stream
    padding) are dropped. Fully vectorized: one sort + two scatter reductions.
    """
    c = chunk.shape[-1]
    srt = jnp.sort(chunk)
    start = jnp.concatenate([jnp.ones((1,), bool), srt[1:] != srt[:-1]])
    seg = jnp.cumsum(start) - 1                                  # (C,) segment ids
    weights = jnp.zeros((c,), count_dtype).at[seg].add(1)
    items = jnp.full((c,), jnp.iinfo(jnp.int32).min, jnp.int32).at[seg].max(srt)
    valid = (items != EMPTY) & (weights > 0)
    items = jnp.where(valid, items, EMPTY)
    weights = jnp.where(valid, weights, 0)
    return items, weights


def merge_pool(s: Summary, cand_items, cand_counts, cand_errors) -> Summary:
    """top-k prune of (summary ∪ candidates) — the eviction step, vectorized.

    Replaces the paper's min-heap eviction: concatenate the updated summary
    with candidate entries and keep the k largest counters (lax.top_k).
    Invalid candidates must carry count < 0 so they can never displace a real
    (or even an empty, count-0) counter.
    """
    k = s.k
    pool_counts = jnp.concatenate([s.counts, cand_counts])
    pool_items = jnp.concatenate([s.items, cand_items])
    pool_errors = jnp.concatenate([s.errors, cand_errors])
    top_counts, idx = lax.top_k(pool_counts, k)
    top_items = jnp.take(pool_items, idx)
    top_errors = jnp.take(pool_errors, idx)
    # a slot that "won" with a negative count is an invalid candidate —
    # only possible when k > |valid pool|; normalize it back to an empty slot.
    neg = top_counts < 0
    zero = jnp.zeros((), s.counts.dtype)
    return Summary(
        items=jnp.where(neg, EMPTY, top_items),
        counts=jnp.where(neg, zero, top_counts),
        errors=jnp.where(neg, zero, top_errors),
    )


def absorb_pool(s: Summary, cand_items: jax.Array, cand_counts: jax.Array,
                cand_errors: jax.Array | None = None, *, m2=0,
                match_fn=None) -> Summary:
    """The shared merge primitive: match → COMBINE offsets → top-k prune.

    Absorbs a candidate set (any zero-error histogram OR another summary's
    counters) into ``s`` with the Cafaro et al. COMBINE offsets:

      item in both:        f̂ ← f̂₁ + f̂₂       ε ← ε₁ + ε₂
      s-only item:         f̂ ← f̂₁ + m₂       ε ← ε₁ + m₂
      candidate-only item: f̂ ← f̂₂ + m₁       ε ← ε₂ + m₁

    where ``m2`` is the candidates' min frequency (0 for an exact histogram
    — then ``cand_errors=None`` skips the errors channel entirely) and m₁ is
    ``min_frequency(s)``. Every reduction path — chunk update,
    ``merge_histogram``, ``combine`` and through them all mesh combinators —
    flows through this one function, so ``match_fn`` (the engine-resolved
    kernel, contract of ``kernels.ops.combine_match``) governs every merge.
    """
    if match_fn is None:
        from repro.kernels import ops as _kops
        match_fn = _kops.combine_match
    dtype = s.counts.dtype
    m1 = min_frequency(s)
    add_c, add_e, matched_s, matched_c = match_fn(
        s.items, cand_items, cand_counts, cand_errors)

    valid1 = s.items != EMPTY
    m2 = jnp.asarray(m2, dtype)
    zero = jnp.zeros((), dtype)
    inc_c = jnp.where(matched_s, add_c.astype(dtype), m2)
    inc_e = jnp.where(matched_s, zero if add_e is None else add_e.astype(dtype),
                      m2)
    upd = Summary(
        items=s.items,
        counts=jnp.where(valid1, s.counts + inc_c, 0),
        errors=jnp.where(valid1, s.errors + inc_e, 0),
    )

    # only unmatched valid candidates survive into the pool (+m₁ offsets);
    # invalid ones carry count -1 so top_k can never pick them over a real
    # (or even an empty, count-0) counter.
    cand_valid = (cand_items != EMPTY) & ~matched_c
    ce = zero if cand_errors is None else cand_errors.astype(dtype)
    cand = (
        jnp.where(cand_valid, cand_items, EMPTY),
        jnp.where(cand_valid, cand_counts.astype(dtype) + m1,
                  jnp.asarray(-1, dtype)),
        jnp.where(cand_valid, ce + m1, 0),
    )
    return merge_pool(upd, *cand)


def merge_histogram(s: Summary, h_items: jax.Array, h_weights: jax.Array,
                    *, match_fn=None) -> Summary:
    """Merge an EXACT histogram into a summary (COMBINE with m₂ = 0).

    An exact histogram is a zero-error summary whose unmonitored items have
    frequency exactly 0, so the absorb-pool offsets reduce to:
      item in both:        f̂ ← f̂ + w        ε unchanged
      summary-only item:   f̂ ← f̂ + 0        ε unchanged
      histogram-only item: f̂ ← w + m₁       ε ← m₁
    ``match_fn`` has the :func:`repro.kernels.ops.combine_match` contract
    (the errors channel is skipped via ``cand_errors=None``).
    """
    return absorb_pool(s, h_items, h_weights, None, m2=0, match_fn=match_fn)


def update_chunk(s: Summary, chunk: jax.Array, *, match_fn=None) -> Summary:
    """Process one chunk of the stream: histogram + vectorized merge."""
    h_items, h_weights = chunk_histogram(chunk, count_dtype=s.counts.dtype)
    return merge_histogram(s, h_items, h_weights, match_fn=match_fn)


@functools.partial(jax.jit, static_argnames=("chunk_size",))
def spacesaving_chunked(s: Summary, stream: jax.Array, *,
                        chunk_size: int = 4096) -> Summary:
    """TPU-native Space Saving: ``lax.scan`` over fixed-size chunks.

    ``stream`` length must be a multiple of ``chunk_size``; pad with EMPTY
    (see :func:`pad_stream`). Each scan step is sort + histogram + matmul-like
    match + top_k — dense, MXU/VPU-friendly work, no data-dependent control
    flow. This is the per-worker block pass of the paper's Algorithm 1.
    """
    n = stream.shape[-1]
    assert n % chunk_size == 0, (n, chunk_size)
    chunks = stream.reshape(n // chunk_size, chunk_size)

    def body(carry, chunk):
        return update_chunk(carry, chunk), None

    out, _ = lax.scan(body, s, chunks)
    return out


def pvary_summary(s: Summary, axis_names) -> Summary:
    """Mark a (replicated) summary as device-varying inside ``jax.shard_map``.

    JAX tracks varying-manual-axes: a freshly built init summary is
    unvarying, but a scan carry that went through per-shard updates is
    varying, so the init must be promoted with ``lax.pcast`` first.
    """
    return jax.tree.map(
        lambda a: lax.pcast(a, tuple(axis_names), to="varying"), s)


def pad_stream(stream: jax.Array, multiple: int) -> jax.Array:
    """Right-pad a stream with EMPTY so its length divides ``multiple``."""
    n = stream.shape[-1]
    rem = (-n) % multiple
    if rem == 0:
        return stream
    return jnp.concatenate([stream, jnp.full((rem,), EMPTY, stream.dtype)])


# ---------------------------------------------------------------------------
# Queries / reporting
# ---------------------------------------------------------------------------

def bounded_estimates(s: Summary, f: jax.Array, eps: jax.Array,
                      monitored: jax.Array):
    """Raw query-kernel outputs → the (f̂, lower, monitored) triple.

    The one place the estimate bound semantics live (shared by
    ``core.estimate``, ``SketchEngine.estimate`` and the QueryFrontend):
    unmonitored items report the min counter m — an upper bound on any
    unmonitored item's true frequency — with lower bound 0; monitored
    items report (f̂, f̂ − ε). Thus lower ≤ f ≤ f̂ always holds.
    """
    m = min_frequency(s)
    f_hat = jnp.where(monitored, f, m)
    lower = jnp.where(monitored, f - eps, jnp.zeros((), f.dtype))
    return f_hat, lower, monitored


def estimate(s: Summary, queries: jax.Array):
    """(f̂, guaranteed-lower-bound, monitored?) for a batch of item ids."""
    eq = (s.items[:, None] == queries[None, :]) & (s.items != EMPTY)[:, None]
    monitored = eq.any(axis=0)
    f = (eq * s.counts[:, None]).sum(axis=0)
    eps = (eq * s.errors[:, None]).sum(axis=0)
    return bounded_estimates(s, f, eps, monitored)


def prune(s: Summary, n: int, k_majority: int):
    """Paper's PRUNED step: candidates with f̂ ≥ ⌊n/k⌋+1.

    Returns (items, f̂, candidate_mask, guaranteed_mask); ``guaranteed`` uses
    the per-counter lower bound f̂ − ε, i.e. items certain to be k-majority.

    Degenerate inputs are well-defined: an all-EMPTY summary or n = 0 (no
    items ingested yet) yield empty masks — EMPTY slots are excluded
    outright and their zero counts can never reach the ≥ 1 threshold.
    """
    if not isinstance(k_majority, jax.Array) and int(k_majority) < 1:
        raise ValueError(f"k_majority must be >= 1, got {k_majority}")
    thresh = n // k_majority + 1
    cand = (s.items != EMPTY) & (s.counts >= thresh)
    guaranteed = cand & (s.counts - s.errors >= thresh)
    return s.items, s.counts, cand, guaranteed


def sort_summary(s: Summary, ascending: bool = True) -> Summary:
    """Order counters by frequency (the paper keeps summaries min-first)."""
    key = jnp.where(s.items == EMPTY,
                    jnp.iinfo(jnp.int32).max if ascending else -1, s.counts)
    idx = jnp.argsort(key if ascending else -key)
    return Summary(items=s.items[idx], counts=s.counts[idx], errors=s.errors[idx])
