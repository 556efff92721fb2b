"""EngineConfig — one place for every sketching policy knob.

Before the engine existed, each consumer picked its own chunk size, match
kernel and reduction at the call site (train/sketch.py, launch/serve.py and
the examples all hand-rolled slightly different defaults). EngineConfig
centralizes:

  * geometry   — counters ``k``, tenant count ``tenants`` (B), chunk ``chunk``
                 (C) and buffer depth ``buffer_depth`` (T);
  * flush mode — ``'deferred'`` (one merge per T-chunk window, QPOPSS-style
                 amortization) or ``'replay'`` (per-chunk merge semantics,
                 still executed as one fused scan at flush time);
  * kernels    — ``'auto' | 'pallas' | 'jnp' | 'sorted'`` resolved ONCE here
                 and threaded to every match/query call the engine makes —
                 including the COMBINE inside every reduction strategy
                 (the unified merge core, DESIGN.md §6.3);
  * reduction  — a name in the reduction registry (engine/reductions.py).

The dataclass is frozen and hashable so it can be captured statically by
jitted closures.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax.numpy as jnp

KERNELS = ("auto", "pallas", "jnp", "sorted", "fused", "sortjoin")
FLUSH_MODES = ("deferred", "replay")

# 'auto' resolution is owned by the PlanService (repro.plan): a measured,
# fingerprint-cached plan when one exists, else the documented static
# heuristic (Pallas on TPU, sorted past plan.SORTED_MIN_K off-TPU). Read
# lazily in resolved_kernel so importing this module never pulls the
# Pallas kernel stack.


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static configuration of one :class:`~repro.engine.SketchEngine`."""

    k: int = 2048                  # counters per tenant summary
    tenants: int = 1               # B — concurrent sketches (mesh groups,
                                   # serving shards, example workers, ...)
    chunk: int = 2048              # C — stream elements per buffered chunk
    buffer_depth: int = 8          # T — chunks buffered between merges
    flush_mode: str = "deferred"   # 'deferred' | 'replay'
    reduction: str = "local"       # key into the reduction registry
    kernel: str = "auto"           # 'auto' or an impl in KERNELS
    axis_names: Tuple[str, ...] = ()   # mesh axes for distributed reductions
    count_dtype: str = "int32"     # dtype name (kept as str: hashable)
    donate_state: bool = False     # donate the state arg of update/flush/
                                   # ingest jits (in-place buffer reuse for
                                   # exclusive-ownership ingestion loops)

    def __post_init__(self):
        if self.k <= 0 or self.tenants <= 0 or self.chunk <= 0:
            raise ValueError(f"k/tenants/chunk must be positive: {self}")
        if self.buffer_depth <= 0:
            raise ValueError(f"buffer_depth must be >= 1, got "
                             f"{self.buffer_depth}")
        if self.flush_mode not in FLUSH_MODES:
            raise ValueError(f"flush_mode {self.flush_mode!r} not in "
                             f"{FLUSH_MODES}")
        if self.kernel not in KERNELS:
            raise ValueError(f"kernel {self.kernel!r} not in {KERNELS}")
        from repro.engine.reductions import reduction_names
        if self.reduction not in reduction_names():
            raise ValueError(f"reduction {self.reduction!r} not registered; "
                             f"have {sorted(reduction_names())}")

    # -- resolved properties ------------------------------------------------

    @property
    def dtype(self):
        return jnp.dtype(self.count_dtype)

    def resolved_kernel(self) -> str:
        """Collapse 'auto' to a concrete impl for the current backend.

        Resolution goes through the PlanService on the ``combine`` op —
        the engine's hot path is the merge window, and one impl governs
        every match/COMBINE/query it dispatches (bitwise-identical across
        impls, so this is purely a speed decision). ``'fused'`` is a valid
        answer: the sub-op wrappers (``combine_match``/``query``) degrade
        it to the megakernel's internal sorted matcher, while the window-
        level surfaces (flush, batched pairwise COMBINE) run the real
        megakernel.
        """
        if self.kernel != "auto":
            return self.kernel
        from repro.plan import resolve_impl
        return resolve_impl("combine", self.k)

    def resolved_flush_kernel(self) -> str:
        """The impl of the window-level flush (``ops.ingest_window``).

        An explicit ``kernel=`` pins it; ``'auto'`` resolves through the
        plan's dedicated ``"flush"`` table — the one place a measured
        plan routes the fused megakernel in where it won, independently
        of the sub-op combine choice.
        """
        if self.kernel != "auto":
            return self.kernel
        from repro.plan import resolve_impl
        return resolve_impl("flush", self.k)

    def window_fn(self):
        """The window-level flush every deferred merge in this engine uses.

        Returns a ``(summary (B,k), window (B,W)) -> Summary`` callable
        over ``kernels.ops.ingest_window`` under the resolved flush impl —
        the megakernel when the plan (or an explicit ``kernel='fused'``)
        says so, the separate-dispatch vmapped merge otherwise. Bitwise-
        identical across impls either way.
        """
        import functools as _ft

        from repro.core.spacesaving import Summary
        from repro.kernels import ops as kops
        ingest = _ft.partial(kops.ingest_window,
                             impl=self.resolved_flush_kernel())

        def window_fn(summary, window):
            return Summary(*ingest(summary.items, summary.counts,
                                   summary.errors, window))
        return window_fn

    def pair_fn(self):
        """Batched pairwise COMBINE for the reduction tree, or None.

        Non-None only when the flush resolved to the fused megakernel:
        then every reduction round runs as one ``ss_ingest`` combine
        launch per pair batch instead of the vmapped library COMBINE
        (same bits). Returns ``(Summary, Summary) -> Summary`` on
        batched (half, k) stacks.
        """
        if self.resolved_flush_kernel() != "fused":
            return None
        from repro.core.spacesaving import Summary
        from repro.kernels import ops as kops

        def pair_fn(s1, s2):
            return Summary(*kops.combine_summaries(
                s1.items, s1.counts, s1.errors,
                s2.items, s2.counts, s2.errors, impl="fused"))
        return pair_fn

    def match_fn(self):
        """The combine-match kernel every merge in this engine uses.

        One callable (``kernels.ops.combine_match`` contract) covers the
        whole merge surface: chunk-window flushes, histogram absorbs, and
        summary-vs-summary COMBINE inside every reduction strategy — so
        ``kernel=`` governs ``merged()``/reductions, not just ingestion.
        """
        from repro.kernels import ops as kops
        return functools.partial(kops.combine_match,
                                 impl=self.resolved_kernel())

    def query_fn(self):
        """The query kernel every estimate in this engine uses."""
        from repro.kernels import ops as kops
        return functools.partial(kops.query, impl=self.resolved_kernel())
