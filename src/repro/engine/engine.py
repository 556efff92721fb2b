"""SketchEngine — batched multi-tenant Space Saving with deferred merges.

One engine owns B concurrent sketches (mesh groups, serving replicas,
example workers — "tenants") and the whole update policy:

    update(state, chunk)        append one (B, C) chunk — O(append), no merge
    flush(state)                force the pending window into the summaries
    ingest(state, stream)       pad/chunk a (B, N) stream, fused update loop
    absorb_histogram(state, …)  merge an exact histogram directly (m₂ = 0)
    merged(state)               flush view + reduction strategy → one Summary
    top(state, n)               heavy hitters of the merged summary
    estimate(state, queries)    (f̂, lower bound, monitored) per query id
    snapshot(state)             publish an immutable versioned QuerySnapshot
                                (the read-side handoff — repro.service)

Consumers (train/sketch.py, launch/serve.py, examples, benchmarks) hold an
engine + a :class:`SketchState` pytree and never touch vmap/merge plumbing
directly.  All methods are jitted and shape-polymorphic in the tenant dim —
a merge-only engine can serve states of any B.

Update cost model (the QPOPSS argument, DESIGN.md §6): an ``update`` call
only appends to the (B, T, C) buffer; the sort + match + top_k merge runs
once per T chunks over the whole window, so merge cost is amortized T× and
the one top_k sees the (T·C) window at once instead of T small pools.
"""
from __future__ import annotations

import functools
import inspect
import itertools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.spacesaving import (EMPTY, Summary, bounded_estimates,
                                    merge_histogram, pad_stream,
                                    sort_summary)
from repro.engine.config import EngineConfig
from repro.engine.reductions import get_reduction
from repro.engine.state import (SketchState, empty_buffer, flushed_summary,
                                init_state, replayed_summary)
from repro.obs import metrics as obs_metrics


def _accepts_kwarg(fn, name: str) -> bool:
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False
    return (name in params
            or any(p.kind is inspect.Parameter.VAR_KEYWORD
                   for p in params.values()))


class SketchEngine:
    """Stateless orchestrator: all stream state lives in SketchState."""

    def __init__(self, config: EngineConfig):
        self.config = config
        self._match_fn = config.match_fn()
        self._query_fn = config.query_fn()
        # the window-level flush dispatch (possibly the fused megakernel)
        # governs the deferred merge; replay mode keeps the per-chunk
        # match_fn path (its scan granularity is a chunk, not a window)
        self._window_fn = (config.window_fn()
                           if config.flush_mode == "deferred" else None)
        # the engine-resolved kernel drives the COMBINEs inside the
        # reduction too (unified merge core); reductions registered with
        # the legacy (stacked, axis_names) signature still work. A fused
        # flush additionally swaps the reduction's local tree rounds to
        # the megakernel's batched pairwise COMBINE (same bits).
        reduce_fn = get_reduction(config.reduction)
        if _accepts_kwarg(reduce_fn, "match_fn"):
            reduce_fn = functools.partial(reduce_fn, match_fn=self._match_fn)
        pair_fn = config.pair_fn()
        if pair_fn is not None and _accepts_kwarg(reduce_fn, "pair_fn"):
            reduce_fn = functools.partial(reduce_fn, pair_fn=pair_fn)
        self._reduce = reduce_fn
        # jit once per engine; shapes re-trace as needed. donate_state
        # aliases the state argument's buffers into the outputs of the
        # three state-threading programs (update/flush/ingest) — only safe
        # for callers that never reuse the passed-in state, which is why
        # it is an explicit opt-in (StreamRuntime.feed's exclusive-
        # ownership loop) and not the default.
        donate = (0,) if config.donate_state else ()
        self.update = jax.jit(self._update, donate_argnums=donate)
        # explicit host-initiated flushes are counted in the process
        # registry (deferred auto-flushes run inside jitted programs and
        # are derivable as ingested_chunks / buffer_depth); the wrapper
        # keeps self.flush's call signature identical
        self._m_flushes = obs_metrics.DEFAULT.counter("engine.flush_calls")
        self._m_snapshots = obs_metrics.DEFAULT.counter(
            "engine.snapshot_publishes")
        _flush_jit = jax.jit(self._flush, donate_argnums=donate)

        def _counted_flush(state):
            self._m_flushes.inc()
            return _flush_jit(state)

        self.flush = _counted_flush
        self.ingest = jax.jit(self._ingest, donate_argnums=donate)
        self.merged = jax.jit(self._merged)
        self.absorb_histogram = jax.jit(self._absorb_histogram)
        self.estimate = jax.jit(self._estimate)
        self.top = jax.jit(self._top, static_argnames=("n",))
        self._snapshot_arrays = jax.jit(self._snapshot_impl)
        self._versions = itertools.count(1)   # per-engine publish counter

    # -- construction -------------------------------------------------------

    def init(self) -> SketchState:
        c = self.config
        return init_state(c.k, c.tenants, c.buffer_depth, c.chunk,
                          count_dtype=c.dtype)

    def state_shapes(self) -> SketchState:
        return jax.eval_shape(self.init)

    # -- updates ------------------------------------------------------------

    def _flush_view(self, state: SketchState) -> Summary:
        """The summaries as if the pending buffer were merged now (pure).

        Traced under the ``sketch.flush`` name scope; its stages carry
        ``sketch.histogram``, ``sketch.match``, ``sketch.absorb`` and
        ``sketch.topk`` (``core/spacesaving.py``), so a profile's per-op
        events name the flush stage they belong to.
        """
        with jax.named_scope("sketch.flush"):
            if self.config.flush_mode == "deferred":
                return flushed_summary(state, match_fn=self._match_fn,
                                       window_fn=self._window_fn)
            return replayed_summary(state, match_fn=self._match_fn)

    def _flush(self, state: SketchState) -> SketchState:
        return SketchState(summary=self._flush_view(state),
                           buffer=empty_buffer(state),
                           fill=jnp.zeros((), jnp.int32),
                           n=state.n)

    def _update(self, state: SketchState, chunk: jax.Array) -> SketchState:
        """Append one chunk per tenant; auto-flush when the buffer fills.

        ``chunk`` is (B, c) with c <= C (EMPTY-padded up to C), or (c,) when
        the engine has a single tenant.
        """
        b, t, c = state.buffer.shape
        if chunk.ndim == 1:
            chunk = chunk[None, :]
        assert chunk.shape[0] == b, (chunk.shape, state.buffer.shape)
        assert chunk.shape[1] <= c, (chunk.shape, state.buffer.shape)
        chunk = jax.vmap(lambda ch: pad_stream(ch, c))(
            chunk.astype(jnp.int32))
        buf = lax.dynamic_update_slice(
            state.buffer, chunk[:, None, :], (0, state.fill, 0))
        appended = SketchState(
            summary=state.summary,
            buffer=buf,
            fill=state.fill + 1,
            n=state.n + (chunk != EMPTY).sum(-1).astype(state.n.dtype),
        )
        return lax.cond(appended.fill >= t, self._flush,
                        lambda s: s, appended)

    def _ingest(self, state: SketchState, stream: jax.Array) -> SketchState:
        """Feed a whole (B, N) stream through the buffered update path."""
        b, t, c = state.buffer.shape
        if stream.ndim == 1:
            stream = stream[None, :]
        assert stream.shape[0] == b, (stream.shape, state.buffer.shape)
        stream = jax.vmap(lambda s: pad_stream(s, c))(
            stream.astype(jnp.int32))
        chunks = stream.reshape(b, -1, c)            # (B, nC, C)
        def body(st, ch):                            # ch: (B, C)
            return self._update(st, ch), None
        out, _ = lax.scan(body, state, jnp.moveaxis(chunks, 1, 0))
        return out

    def _absorb_histogram(self, state: SketchState, items: jax.Array,
                          weights: jax.Array) -> SketchState:
        """Merge an EXACT histogram straight into the summaries (m₂ = 0).

        For producers that already aggregated their stream (e.g. MoE router
        expert counts): no buffering — the histogram is one pre-reduced
        chunk.  ``items``/``weights`` are (B, E), or (E,) broadcast to all
        tenants.
        """
        b = state.tenants
        if items.ndim == 1:
            items = jnp.broadcast_to(items[None], (b,) + items.shape)
            weights = jnp.broadcast_to(weights[None], (b,) + weights.shape)
        summary = jax.vmap(
            lambda s, i, w: merge_histogram(s, i, w,
                                            match_fn=self._match_fn))(
                state.summary, items,
                weights.astype(state.summary.counts.dtype))
        valid = (items != EMPTY) & (weights > 0)
        n = state.n + jnp.where(valid, weights, 0).sum(-1).astype(
            state.n.dtype)
        return SketchState(summary, state.buffer, state.fill, n)

    # -- queries ------------------------------------------------------------

    def _merged(self, state: SketchState, axes=None) -> Summary:
        """One global summary: flush view, then the reduction strategy.

        Device-resident cheap path (DESIGN.md §13): when ``fill == 0``
        the pending window is all-EMPTY by construction (``_update``
        auto-flushes and resets exactly when the buffer fills, and flush
        resets to the EMPTY buffer), so the window-level merge would be
        an identity pass over T·C EMPTY slots — the dominant cost of a
        block-boundary snapshot. The cond skips it and pays only the
        reduction, bitwise-identically (merging an EMPTY window never
        changes a summary; asserted per kernel × flush mode in
        tests/test_serve.py). Traced under the ``sketch.merge`` name
        scope (the publish merge of ``StreamRuntime.merged``).

        ``axes`` replaces the configured mesh axes; ``()`` stops at the
        lane reduce, which is the sharded runtime's first publish program
        (its second, the exchange, runs the strategy across the mesh).
        """
        axes = tuple(self.config.axis_names if axes is None else axes)
        with jax.named_scope("sketch.merge"):
            return lax.cond(
                state.fill == 0,
                lambda st: self._reduce(st.summary, axes),
                lambda st: self._reduce(self._flush_view(st), axes),
                state)

    def _top(self, state: SketchState, n: int = 10):
        # n is clamped to [0, k]: slicing past k would silently return k
        # entries, and a negative n would wrap around.
        s = sort_summary(self._merged(state), ascending=False)
        n = max(0, min(int(n), s.items.shape[-1]))
        return s.items[:n], s.counts[:n]

    def _estimate(self, state: SketchState, queries: jax.Array):
        """(f̂, guaranteed lower bound, monitored?) per query id."""
        s = self._merged(state)
        f, eps, mon = self._query_fn(s.items, s.counts, s.errors, queries)
        return bounded_estimates(s, f, eps, mon)

    # -- snapshot publishing (the read-side handoff, DESIGN.md §7) ----------

    def _snapshot_impl(self, state: SketchState):
        return self._merged(state), state.n.sum(), state.n

    def snapshot(self, state: SketchState, *, lazy: bool = False,
                 version: int | None = None, n_hint: int | None = None,
                 on_materialize=None):
        """Publish an immutable, versioned :class:`QuerySnapshot`.

        Built from the pure flush *view* + the reduction strategy, so the
        pending buffer is fully visible in the snapshot but ``state`` is
        NOT flushed or otherwise mutated — ingestion keeps appending to the
        same buffer while readers query the frozen view. Each publish from
        this engine gets the next version number (monotonic, host-side;
        ``version`` pins it for deferred republication).

        ``lazy=True`` returns a :class:`LazyQuerySnapshot` instead: the
        write path captures only the state reference + cheap scalars
        (``n_hint`` feeds the ``count_floor`` ε filter) and the reduction
        runs on the first reader. The caller must uphold the donation
        fence — the state passed here must never be donated to a later
        program (``IngestLoop`` runs one non-donated ingest after every
        publish, which is exactly that guarantee).
        """
        from repro.service.snapshot import publish, publish_lazy
        if version is None:
            version = next(self._versions)
        self._m_snapshots.inc()
        if lazy:
            c = self.config
            return publish_lazy(
                lambda: self._eager_snapshot(state, version),
                version=version, kernel=c.resolved_kernel(), k=c.k,
                n_hint=n_hint, on_materialize=on_materialize)
        return self._eager_snapshot(state, version)

    def _eager_snapshot(self, state: SketchState, version: int):
        from repro.service.snapshot import publish
        summary, n_total, shard_n = self._snapshot_arrays(state)
        return publish(summary, n_total, shard_n, version=version,
                       kernel=self.config.resolved_kernel())
