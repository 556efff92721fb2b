"""ExecutionPlan — one immutable, auditable answer to every "auto".

Before the PlanService, "auto" was resolved by scattered inline heuristics:
``kernels/ops.py`` hardcoded the dense↔sorted crossover at k >= 256,
``EngineConfig.resolved_kernel`` duplicated it, and RuntimeConfig fell back
to whatever reduction the engine declared regardless of axis size. The
paper's own result (the Xeon beats the Phi for the same algorithm) says
those choices are architecture-dependent — so a plan either comes from
*measurement* (``source == "measured"``, built by ``repro.launch.tune``
from calibrated probes) or is the documented zero-measurement fallback
(``source == "static"``) that reproduces the old heuristics exactly.

A plan stores *decisions*, not raw probe data (that goes to
BENCH_plan.json): per-op kernel choices at the probed counter budgets,
per-axis-size reduction strategies and pod splits, the recommended chunk /
buffer geometry, and the frontend's query bucketing floor. Lookups between
probed points snap to the nearest probed value in log-space — crossovers
are monotone in k on every backend we probe, so nearest-grid resolution is
the right interpolation for a categorical choice.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import tempfile
from pathlib import Path
from typing import Mapping

PLAN_FORMAT = 1

#: ops with a dispatchable kernel choice (kernels/ops.py wrappers);
#: 'flush' is the window-level merge (ops.ingest_window — the engine's
#: whole deferred-flush dispatch), where the fused megakernel competes
#: against the separate-dispatch impls
PLAN_OPS = ("update", "combine", "query", "flush")

#: concrete impls a plan may route to (kernels/ops.py dispatch targets);
#: anything else would fall through ops.py's dispatch to the Pallas branch
#: silently, so plans validate their tables against this up front.
#: 'fused' (kernels/ss_ingest.py) is measurement-only: static_impl never
#: returns it — it reaches a table exclusively through a probe that timed
#: it on the running backend (the paper's Xeon-vs-Phi discipline).
PLAN_IMPLS = ("pallas", "jnp", "sorted", "fused", "sortjoin")

# the dense k×c match is near-quadratic in k; below this counter budget it
# beats sort+searchsorted on CPU (measured in BENCH_sketch.json). This is
# THE static fallback threshold — the former inline rule of kernels/ops.py
# and EngineConfig, now owned by the plan layer.
SORTED_MIN_K = 256

# on TPU the flush's dense Pallas match does k·T·C compares per lane; the
# two-sort merge-join (kernels/ref.py combine_match_sortjoin) costs two
# sorts of k + T·C entries, nearly flat in k. On a TPU v5e at the plan's
# window (T·C = 8 × 2048, 8 lanes) the two matches tie at 512 counters
# (0.47 and 0.48 ms a flush) and the join wins above: 0.47 against 0.74 ms
# at 1024, against 5.71 ms at 8000 (PERF.md §6).
SORTJOIN_MIN_K = 512


def _nearest_log(keys, x: int) -> int:
    """The probed grid point nearest to ``x`` in log-space."""
    return min(keys, key=lambda p: (abs(math.log2(max(x, 1) / p)), p))


def static_impl(op: str, k: int, *, on_tpu: bool | None = None) -> str:
    """The zero-measurement kernel heuristic (the pre-plan behavior).

    TPU → the Pallas kernels control VMEM tiling, except the ``flush``
    from SORTJOIN_MIN_K counters up: there the two-sort merge-join beats
    the dense Pallas match on the chip (combine and query keep Pallas;
    their candidate side is k ids or a query batch, not a window).
    Off-TPU the vectorized jnp path wins at small k and the sorted
    merge-join past SORTED_MIN_K for combine/query. ``update``
    (match_weights) always takes the dense jnp path off-TPU: its
    histogram side is small enough that the sort never paid for itself
    in the seed measurements. Off-TPU ``flush`` (the window-level merge)
    follows combine's rule — it is a combine-match dispatched over the
    window histogram. No static rule picks the fused megakernel: its
    body contains sort/scatter/top_k, which only an actual measurement
    can certify on a given backend.
    """
    if op not in PLAN_OPS:
        raise ValueError(f"op {op!r} not in {PLAN_OPS}")
    if on_tpu is None:
        import jax
        on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        return ("sortjoin" if op == "flush" and k >= SORTJOIN_MIN_K
                else "pallas")
    if op == "update":
        return "jnp"
    return "sorted" if k >= SORTED_MIN_K else "jnp"


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Immutable per-backend decision table (see module docstring)."""

    fingerprint: str
    source: str                              # 'measured' | 'static'
    kernels: Mapping[str, Mapping[int, str]]  # op -> {probed k -> impl}
    reductions: Mapping[int, str]            # axis size p -> strategy
    pods: Mapping[int, int]                  # axis size p -> pod split
    chunk: int = 2048                        # recommended C
    buffer_depth: int = 8                    # recommended T
    query_min_batch: int = 16                # QueryFrontend bucket floor
    publish_every: int = 8                   # serving: blocks per ring publish
    ring_depth: int = 4                      # serving: SnapshotRing slots
    feed_depth: int = 2                      # host→device staging slots
    lazy_publish: bool = False               # serving: defer snapshot reduce
    format: int = PLAN_FORMAT

    def __post_init__(self):
        if self.source not in ("measured", "static"):
            raise ValueError(f"source {self.source!r} not in "
                             f"('measured', 'static')")
        bad = set(self.kernels) - set(PLAN_OPS)
        if bad:
            raise ValueError(f"unknown plan ops {sorted(bad)}; have "
                             f"{PLAN_OPS}")
        for op, table in self.kernels.items():
            bad_impls = set(table.values()) - set(PLAN_IMPLS)
            if bad_impls:
                # a typo'd impl in a hand-pinned plan must fail here, not
                # silently dispatch the interpret-mode Pallas kernel
                raise ValueError(
                    f"plan op {op!r} routes to unknown impl(s) "
                    f"{sorted(bad_impls)}; have {PLAN_IMPLS}")
        if self.chunk <= 0 or self.buffer_depth <= 0 \
                or self.query_min_batch <= 0:
            raise ValueError(
                f"chunk/buffer_depth/query_min_batch must be positive: "
                f"{self.chunk}/{self.buffer_depth}/{self.query_min_batch}")
        if self.publish_every <= 0 or self.ring_depth <= 0:
            raise ValueError(
                f"publish_every/ring_depth must be positive: "
                f"{self.publish_every}/{self.ring_depth}")
        if self.feed_depth < 1:
            raise ValueError(
                f"feed_depth must be >= 1, got {self.feed_depth}")
        if not isinstance(self.lazy_publish, bool):
            raise ValueError(
                f"lazy_publish must be a bool, got {self.lazy_publish!r}")

    # -- resolution ----------------------------------------------------------

    def impl_for(self, op: str, k: int) -> str:
        """The kernel impl this plan picks for ``op`` at counter budget k."""
        table = self.kernels.get(op) or {}
        if not table:
            return static_impl(op, k)
        return table[_nearest_log(table.keys(), k)]

    def reduction_for(self, p: int) -> str:
        """The cross-shard strategy for a p-wide reduction axis."""
        if p <= 1:
            return "local"
        if not self.reductions:
            # the pre-plan default: recursive doubling, which itself
            # degrades to allgather on non-power-of-two axes
            return "butterfly"
        return self.reductions[_nearest_log(self.reductions.keys(), p)]

    def pods_for(self, p: int) -> int:
        """The pod split for p shards (1 → flat single-pod mesh)."""
        if p <= 1 or not self.pods:
            return 1
        pods = self.pods[_nearest_log(self.pods.keys(), p)]
        return pods if pods >= 1 and p % pods == 0 else 1

    # -- serialization -------------------------------------------------------

    def to_json(self) -> dict:
        return {
            "format": self.format,
            "fingerprint": self.fingerprint,
            "source": self.source,
            "kernels": {op: {str(k): impl for k, impl in sorted(tbl.items())}
                        for op, tbl in self.kernels.items()},
            "reductions": {str(p): s
                           for p, s in sorted(self.reductions.items())},
            "pods": {str(p): n for p, n in sorted(self.pods.items())},
            "chunk": self.chunk,
            "buffer_depth": self.buffer_depth,
            "query_min_batch": self.query_min_batch,
            "publish_every": self.publish_every,
            "ring_depth": self.ring_depth,
            "feed_depth": self.feed_depth,
            "lazy_publish": self.lazy_publish,
        }

    @classmethod
    def from_json(cls, d: dict) -> "ExecutionPlan":
        if d.get("format") != PLAN_FORMAT:
            raise ValueError(
                f"plan format {d.get('format')!r} != {PLAN_FORMAT}; "
                f"re-run `python -m repro.launch.tune`")
        return cls(
            fingerprint=d["fingerprint"],
            source=d["source"],
            kernels={op: {int(k): impl for k, impl in tbl.items()}
                     for op, tbl in d.get("kernels", {}).items()},
            reductions={int(p): s
                        for p, s in d.get("reductions", {}).items()},
            pods={int(p): int(n) for p, n in d.get("pods", {}).items()},
            chunk=int(d.get("chunk", 2048)),
            buffer_depth=int(d.get("buffer_depth", 8)),
            query_min_batch=int(d.get("query_min_batch", 16)),
            # serving knobs arrived after format 1 shipped; absent keys
            # (older cached plans) fall back to the static defaults
            publish_every=int(d.get("publish_every", 8)),
            ring_depth=int(d.get("ring_depth", 4)),
            # pipeline knobs arrived with DESIGN.md §13; legacy defaults
            # reproduce the pre-pipeline serving discipline. A
            # ``coalesce_max`` of older plans is ignored: the serving loop
            # groups blocks up to the publish boundary
            feed_depth=int(d.get("feed_depth", 2)),
            lazy_publish=bool(d.get("lazy_publish", False)),
        )

    def save(self, path: os.PathLike | str) -> Path:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        # unique temp + atomic rename: two concurrent tuners for the same
        # fingerprint must each publish a complete file, never a torn one
        fd, tmp = tempfile.mkstemp(dir=path.parent,
                                   prefix=path.name + ".", suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                f.write(json.dumps(self.to_json(), indent=2) + "\n")
            Path(tmp).replace(path)
        except BaseException:
            Path(tmp).unlink(missing_ok=True)
            raise
        return path

    @classmethod
    def load(cls, path: os.PathLike | str) -> "ExecutionPlan":
        return cls.from_json(json.loads(Path(path).read_text()))


def static_plan(fingerprint: str | None = None) -> ExecutionPlan:
    """The zero-measurement fallback plan (the documented old heuristics).

    Empty decision tables mean every lookup routes through
    :func:`static_impl` / the pre-plan reduction default, so behavior with
    no cache present is bitwise-identical to the pre-PlanService tree.
    """
    if fingerprint is None:
        from repro.plan.fingerprint import device_fingerprint
        fingerprint = device_fingerprint()
    return ExecutionPlan(fingerprint=fingerprint, source="static",
                         kernels={}, reductions={}, pods={})
