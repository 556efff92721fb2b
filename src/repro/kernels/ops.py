"""Jit'd public wrappers for the Space Saving kernels.

Dispatch policy (``impl``):
  * ``'auto'``   — resolved through the active :mod:`repro.plan` plan
                   (``resolve_impl(op, k)``): a measured plan picks the
                   impl probed fastest on this backend; with no plan
                   cached, the documented static fallback applies. On TPU
                   that is Pallas, except the window flush
                   (``ingest_window``) from ``plan.SORTJOIN_MIN_K``
                   counters up, which runs ``'sortjoin'``: the dense
                   kernel's k·T·C compares per lane cost more there than
                   the join's two sorts of k + T·C entries. Off-TPU it is
                   the pure-jnp reference below ``plan.SORTED_MIN_K``
                   counters and the sorted merge-join above it
                   (``match_weights`` stays jnp).
  * ``'pallas'`` — force the kernel: compiled by Mosaic on TPU, evaluated
                   in interpret mode on the CPU backend (tests); any other
                   backend raises instead of silently interpreting.
  * ``'jnp'``    — force the reference.
  * ``'sorted'`` — sort + searchsorted merge-join (kernels/ref.py): O((k+c)·
                   log k) instead of the dense k×c matrix; the fast path for
                   large k off-TPU. Requires distinct valid summary items
                   (true of every well-formed summary). Engine code selects
                   this centrally via EngineConfig.kernel (see repro.engine).
  * ``'sortjoin'`` — merge-join by two ``lax.sort`` passes over summary ∪
                   candidates (kernels/ref.py): no k×c intermediate, no
                   scatter, no gather. Requires distinct valid ids on both
                   sides, so ``query`` (whose batches may repeat ids)
                   degrades it to ``'sorted'``.
  * ``'fused'``  — the whole-merge megakernel (kernels/ss_ingest.py): only
                   a real dispatch target for the window-level ops
                   (``ingest_window`` / ``combine_summaries``); at the
                   sub-op surfaces (``match_weights``/``combine_match``/
                   ``query``) it degrades to ``'sorted'`` — the matcher the
                   megakernel runs internally — so a fused-configured
                   engine is well-defined on every path it dispatches.

All wrappers pad inputs to block multiples (EMPTY ids / zero weights are
match-neutral) and strip the padding from the outputs. ``combine_match`` is
the unified matcher behind every merge path (chunk update, histogram absorb
and summary-vs-summary COMBINE — see core/spacesaving.py:absorb_pool).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.ss_combine import combine_match_pallas
from repro.kernels.ss_match import match_weights_pallas
from repro.kernels.ss_query import query_pallas

EMPTY = -1


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Whether Pallas kernels run in interpret mode on this backend.

    Compiled on TPU; interpreted on the CPU backend only (the test and
    rehearsal path). Any other backend is refused: interpreting there
    would hide the device behind a slow emulation instead of failing.
    """
    if _on_tpu():
        return False
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU or interpret on CPU; backend "
        f"{backend!r} is neither — pick impl='jnp' or 'sorted' there")


# -- memoized plan resolution -------------------------------------------------
# resolve_impl sits on the per-dispatch hot path (every traced 'auto' pays
# it), and each uncached call costs a plan-cache stat + table lookup. The
# memo holds the collapsed (op, k) → impl answer and is invalidated by the
# PlanService generation counter, which bumps on install()/clear() — i.e.
# whenever the answer could legitimately change in-process. (A plan-cache
# FILE swapped underneath a running process is picked up on the next
# clear(); the tune CLI clears after publishing, so the normal re-tune flow
# invalidates correctly.)

_resolve_cache: dict = {}      # (op, k) -> impl
_resolve_gen: int | None = None


def resolve_impl(op: str, k: int) -> str:
    """Collapse 'auto' for one op at counter budget k via the active plan.

    Memoizing wrapper over :func:`repro.plan.resolve_impl` (imported
    lazily so the kernel stack never pulls the plan subsystem unless an
    'auto' is actually dispatched) — THE single auto-routing point; the
    former inline ``k >= SORTED_MIN_K`` rules live on only as the plan's
    zero-measurement static fallback (``repro.plan.static_impl``).
    """
    global _resolve_gen
    from repro.plan import service as _svc
    gen = _svc.generation()
    if gen != _resolve_gen:
        _resolve_cache.clear()
        _resolve_gen = gen
    key = (op, int(k))
    impl = _resolve_cache.get(key)
    if impl is None:
        impl = _resolve_cache[key] = _svc.resolve_impl(op, k)
    return impl


def _pad1(a: jax.Array, mult: int, fill) -> jax.Array:
    rem = (-a.shape[0]) % mult
    if rem == 0:
        return a
    return jnp.concatenate([a, jnp.full((rem,), fill, a.dtype)])


def match_weights(s_items: jax.Array, h_items: jax.Array, h_weights: jax.Array,
                  *, impl: str = "auto", block_k: int = 512, block_c: int = 512):
    """See kernels/ss_match.py. Returns (add_w (k,), matched (c,) bool)."""
    if impl == "auto":
        impl = resolve_impl("update", s_items.shape[0])
    if impl == "fused":
        impl = "sorted"      # the megakernel's internal matcher
    if impl == "sorted":
        return _ref.match_weights_sorted(s_items, h_items, h_weights)
    if impl == "sortjoin":
        add_w, _, _, matched = _ref.combine_match_sortjoin(
            s_items, h_items, h_weights)
        return add_w, matched
    if impl == "jnp":
        return _ref.match_weights_ref(s_items, h_items, h_weights)
    k, c = s_items.shape[0], h_items.shape[0]
    bk = min(block_k, max(8, 1 << (k - 1).bit_length()))
    bc = min(block_c, max(128, 1 << (c - 1).bit_length()))
    sp = _pad1(s_items, bk, EMPTY)
    hp = _pad1(h_items, bc, EMPTY)
    wp = _pad1(h_weights.astype(jnp.int32), bc, 0)
    add_w, matched = match_weights_pallas(
        sp, hp, wp, block_k=bk, block_c=bc, interpret=_interpret())
    return add_w[:k].astype(h_weights.dtype), matched[:c]


def combine_match(s_items: jax.Array, c_items: jax.Array,
                  c_counts: jax.Array, c_errors: jax.Array | None = None, *,
                  impl: str = "auto", block_k: int = 512, block_c: int = 512):
    """See kernels/ref.py (contract) / kernels/ss_combine.py (TPU kernel).

    The one matcher behind every merge — summary-vs-summary COMBINE carries
    counts AND errors; the exact-histogram merge passes ``c_errors=None``
    and the errors channel is skipped (ref/sorted) or dropped (pallas).
    Returns (add_c (k,), add_e (k,) | None, matched_s (k,), matched_c (c,)).

    'auto' resolves through the plan (every absorb_pool caller feeds
    well-formed distinct-id summaries/histograms, so any impl the plan
    picks — sorted included — is bitwise-safe here).
    """
    if impl == "auto":
        impl = resolve_impl("combine", s_items.shape[0])
    if impl == "fused":
        impl = "sorted"      # the megakernel's internal matcher
    if impl not in ("sorted", "jnp", "sortjoin"):
        # the Pallas kernel contracts in int32; wider count dtypes would
        # silently truncate, so route them to the (exact) sorted merge-join.
        wide = any(a is not None and jnp.dtype(a.dtype).itemsize > 4
                   for a in (c_counts, c_errors))
        if wide:
            impl = "sorted"
    if impl == "sorted":
        return _ref.combine_match_sorted(s_items, c_items, c_counts, c_errors)
    if impl == "sortjoin":
        return _ref.combine_match_sortjoin(s_items, c_items, c_counts,
                                           c_errors)
    if impl == "jnp":
        return _ref.combine_match_ref(s_items, c_items, c_counts, c_errors)
    k, c = s_items.shape[0], c_items.shape[0]
    bk = min(block_k, max(8, 1 << (k - 1).bit_length()))
    bc = min(block_c, max(128, 1 << (c - 1).bit_length()))
    sp = _pad1(s_items, bk, EMPTY)
    cip = _pad1(c_items, bc, EMPTY)
    ccp = _pad1(c_counts.astype(jnp.int32), bc, 0)
    cep = _pad1((jnp.zeros_like(c_counts) if c_errors is None
                 else c_errors).astype(jnp.int32), bc, 0)
    add_c, add_e, ms, mc = combine_match_pallas(
        sp, cip, ccp, cep, block_k=bk, block_c=bc, interpret=_interpret())
    return (add_c[:k].astype(c_counts.dtype),
            None if c_errors is None else add_e[:k].astype(c_errors.dtype),
            ms[:k], mc[:c])


def query(s_items, s_counts, s_errors, queries, *, impl: str = "auto",
          block_k: int = 512, block_q: int = 512):
    """See kernels/ss_query.py. Returns (f̂, ε, monitored) per query.

    'auto' resolves through the plan like ``combine_match`` (the read path
    probes well-formed distinct-id summaries, so every impl is
    bitwise-safe). Wide count dtypes are routed away from the int32 Pallas
    kernel regardless of what the plan picked — a dtype-safety constraint,
    not a policy choice.
    """
    if impl == "auto":
        impl = resolve_impl("query", s_items.shape[0])
    if impl in ("fused", "sortjoin"):
        # the megakernel's internal matcher; the two-sort join needs
        # distinct ids on both sides, and a query batch may repeat ids
        impl = "sorted"
    if impl not in ("sorted", "jnp"):
        wide = any(jnp.dtype(a.dtype).itemsize > 4
                   for a in (s_counts, s_errors))
        if wide:
            impl = "sorted"
    if impl == "sorted":
        return _ref.query_sorted(s_items, s_counts, s_errors, queries)
    if impl == "jnp":
        return _ref.query_ref(s_items, s_counts, s_errors, queries)
    k, q = s_items.shape[0], queries.shape[0]
    bk = min(block_k, max(8, 1 << (k - 1).bit_length()))
    bq = min(block_q, max(128, 1 << (q - 1).bit_length()))
    sp = _pad1(s_items, bk, EMPTY)
    cp = _pad1(s_counts.astype(jnp.int32), bk, 0)
    ep = _pad1(s_errors.astype(jnp.int32), bk, 0)
    qp = _pad1(queries, bq, EMPTY)
    f_hat, eps, mon = query_pallas(
        sp, cp, ep, qp, block_k=bk, block_q=bq, interpret=_interpret())
    return (f_hat[:q].astype(s_counts.dtype), eps[:q].astype(s_errors.dtype),
            mon[:q])


# -- window-level ops: the fused megakernel's dispatch surfaces ---------------

def _batched(*channels):
    """Promote (n,) channels to (1, n); returns (arrays, was_unbatched)."""
    unbatched = channels[0].ndim == 1
    if unbatched:
        channels = tuple(a[None] for a in channels)
    return channels, unbatched


def ingest_window(s_items: jax.Array, s_counts: jax.Array,
                  s_errors: jax.Array, window: jax.Array, *,
                  impl: str = "auto"):
    """Flush a pending window into batched summaries — the engine's merge.

    ``s_*`` are (B, k) summary channels, ``window`` is the (B, W) pending
    stream window (EMPTY-padded; W = T·C for a deferred engine buffer).
    Unbatched (k,)/(W,) inputs are promoted and squeezed back. Returns the
    updated ``(items, counts, errors)`` triple.

    Every impl computes ``update_chunk(summary_b, window_b)`` exactly —
    bitwise-identical across impls:

      * ``'fused'`` — the ss_ingest megakernel: one Pallas launch over the
        tenant grid, the whole sort/match/absorb/top_k chain VMEM-resident
        (interpret-evaluated off-TPU).
      * ``'pallas'``/``'jnp'``/``'sorted'`` — the separate-dispatch path:
        vmapped ``update_chunk`` with ``combine_match`` forced to that
        impl (what the engine flush always did before the megakernel).

    ``'auto'`` resolves through the plan's ``"flush"`` table — fused is
    only ever planned where a measured probe says it wins (static plans
    never pick it).
    """
    if impl == "auto":
        impl = resolve_impl("flush", s_items.shape[-1])
    (si, sc, se, w), unbatched = _batched(s_items, s_counts, s_errors,
                                          window)
    if impl == "fused":
        from repro.kernels.ss_ingest import fused_ingest_pallas
        out = fused_ingest_pallas(si, sc, se, w, interpret=_interpret())
    else:
        from repro.core.spacesaving import Summary, update_chunk
        match = functools.partial(combine_match, impl=impl)
        res = jax.vmap(lambda s, win: update_chunk(
            Summary(*s), win, match_fn=match))((si, sc, se), w)
        out = (res.items, res.counts, res.errors)
    return tuple(a[0] for a in out) if unbatched else out


def combine_summaries(s1_items: jax.Array, s1_counts: jax.Array,
                      s1_errors: jax.Array, s2_items: jax.Array,
                      s2_counts: jax.Array, s2_errors: jax.Array, *,
                      impl: str = "auto"):
    """Batched pairwise COMBINE — one reduction-tree round, dispatched.

    All six channels are (B, k) (unbatched (k,) promoted). Returns the
    merged ``(items, counts, errors)``. ``'fused'`` runs the whole
    match + offsets + top_k chain as one ss_ingest launch per pair; other
    impls evaluate the library ``combine`` with ``combine_match`` forced
    to that impl — bitwise-identical either way. ``'auto'`` resolves
    through the plan's ``"combine"`` table.
    """
    if impl == "auto":
        impl = resolve_impl("combine", s1_items.shape[-1])
    (a_i, a_c, a_e, b_i, b_c, b_e), unbatched = _batched(
        s1_items, s1_counts, s1_errors, s2_items, s2_counts, s2_errors)
    if impl == "fused":
        from repro.kernels.ss_ingest import fused_combine_pallas
        out = fused_combine_pallas(a_i, a_c, a_e, b_i, b_c, b_e,
                                   interpret=_interpret())
    else:
        from repro.core.combine import combine
        from repro.core.spacesaving import Summary
        match = functools.partial(combine_match, impl=impl)
        res = jax.vmap(lambda s1, s2: combine(
            Summary(*s1), Summary(*s2), match_fn=match))(
                (a_i, a_c, a_e), (b_i, b_c, b_e))
        out = (res.items, res.counts, res.errors)
    return tuple(a[0] for a in out) if unbatched else out
