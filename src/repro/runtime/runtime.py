"""StreamRuntime — the sharded two-level ingestion runtime.

One object owns end-to-end distributed sketching and is the only way
consumers drive it (DESIGN.md §8):

    init()                 sharded SketchState over shards × lanes workers
    decompose(stream)      the canonical (W, per) block decomposition
    ingest(state, stream)  block-decompose + per-shard buffered engine ingest
    feed(state, blocks)    double-buffered host→device ingestion loop
    compile_ingest(widths) both ingest programs for coalesced groups of
                           each width, compiled once per runtime
    merged(state)          one global Summary via the reduction strategy
    snapshot(state)        immutable versioned QuerySnapshot with per-worker
                           provenance (the QueryService handoff)
    frontend()             a QueryFrontend on the runtime's resolved kernel

Two-level structure, mapped onto the paper's hybrid MPI/OpenMP design:

  * shard level — the global stream is block-decomposed over the ``data``
    mesh axis via ``shard_map`` (optionally ``("pod", "data")`` for the
    two-level topology): each shard is an MPI rank with its own
    SketchEngine state slice and pending-chunk buffer.
  * lane level — inside each shard the engine runs ``lanes`` vmapped
    sketches (EngineConfig.tenants): the OpenMP threads of the paper,
    merged on-device by the local COMBINE tree before any communication.

Global snapshots run the engine's reduction strategy (``butterfly`` /
``allgather`` / ``hierarchical`` from the reduction registry) across the
mesh axes. Because every strategy evaluates the same canonical adjacent-pair
COMBINE tree (see ``reduce_summaries``), a sharded runtime snapshot is
bitwise-identical to a single-host SketchEngine over the same shards×lanes
block decomposition — tested across strategies × p × kernel impls in
tests/test_runtime.py and tests/test_sharding_dist.py.

The shard body never returns the replicated ``fill`` scalar through
``shard_map`` (its evolution is deterministic: ``(fill + chunks) % depth``,
computed outside), so every shard output is sharded and no replication
checks are involved.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.parallel import block_decompose
from repro.core.spacesaving import Summary
from repro.engine import SketchEngine
from repro.engine.state import SketchState
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.runtime.config import RuntimeConfig
from repro.runtime.feed import DeviceFeed, host_blocks

# batch feed()'s time-gated history pump (DESIGN.md §14): at most one
# registry sample per interval, regardless of block rate
FEED_SAMPLE_INTERVAL_S = 0.25


def _rank0(tree):
    """Each leaf's buffer on the mesh's first device (rank 0's row of a
    dim-0 sharded array, or that device's copy of a replicated one): no
    program runs and nothing waits."""
    return jax.tree.map(lambda a: a.addressable_data(0), tree)


class StreamRuntime:
    """Sharded two-level ingestion: shard_map ranks × vmapped engine lanes."""

    def __init__(self, config: RuntimeConfig):
        self.config = config
        self.shards = (config.shards if config.shards is not None
                       else len(jax.devices()))
        self.pods = config.resolved_pods(self.shards)
        if self.pods > 1 and self.shards % self.pods:
            raise ValueError(
                f"pods ({self.pods}) must divide shards ({self.shards}, "
                f"auto-sized to the host device count)")
        n_dev = len(jax.devices())
        if self.shards > n_dev:
            raise ValueError(
                f"StreamRuntime: requested {self.shards} shards but only "
                f"{n_dev} host device(s) are available; lower shards or "
                f"force more via "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=N")

        if self.shards == 1:
            # single-shard fast path: no mesh, no shard_map — the engine's
            # vmapped lanes are the whole worker set and every reduction
            # strategy degrades to the local COMBINE tree.
            self.mesh = None
            self._axes = ()
            self._dim0 = None
        elif self.pods > 1:
            from repro.launch.mesh import make_mesh_shape
            self.mesh = make_mesh_shape(
                (self.pods, self.shards // self.pods), ("pod", "data"))
            # innermost (intra-pod) axis first — the reduction registry's
            # axis_names convention; dim-0 sharding is mesh-major.
            self._axes = ("data", "pod")
            self._dim0 = ("pod", "data")
        else:
            from repro.launch.mesh import make_host_mesh
            self.mesh = make_host_mesh(n_data=self.shards)
            self._axes = ("data",)
            self._dim0 = ("data",)

        self.engine = SketchEngine(dataclasses.replace(
            config.engine,
            reduction=config.resolved_reduction(self.shards),
            axis_names=self._axes))
        self._versions = itertools.count(1)
        self._compiled_widths: set[int] = set()
        self._compile_lock = threading.Lock()
        self._build_programs()

    # -- geometry ------------------------------------------------------------

    @property
    def lanes(self) -> int:
        return self.config.lanes

    @property
    def workers(self) -> int:
        """Total logical workers W = shards × lanes."""
        return self.shards * self.lanes

    def decompose(self, stream: jax.Array) -> jax.Array:
        """The canonical (W, per) block decomposition of a global stream."""
        return block_decompose(stream, self.workers, self.config.engine.chunk)

    # -- program construction ------------------------------------------------

    def _build_programs(self):
        eng = self.engine

        if self.shards == 1:
            self._ingest_blocks_fn = jax.jit(eng._ingest)
            # feed()'s loop variant: the state arg is donated, so XLA
            # aliases the (B, T, C) buffer and summary channels in place
            # instead of copying them every step — safe only because the
            # loop-internal states are exclusively owned by feed().
            self._feed_ingest_fn = jax.jit(eng._ingest, donate_argnums=(0,))
            # the jitted pair itself, which compile_ingest lowers: the two
            # attributes above may be wrapped in plain functions (the
            # benchmark's fault tests break the ingest that way)
            self._ingest_programs = (self._ingest_blocks_fn,
                                     self._feed_ingest_fn)
            self._merged_fn = jax.jit(eng._merged)
            return

        spec1 = P(self._dim0)          # dim-0 over the data (or pod×data) axes
        state_specs = (Summary(spec1, spec1, spec1), spec1, spec1)

        def shard_ingest(summary, buffer, n, fill, blocks):
            # reassemble one shard's engine state (lanes tenants) from the
            # sharded leaves + the replicated fill scalar
            st = SketchState(summary=summary, buffer=buffer, fill=fill, n=n)
            out = eng._ingest(st, blocks)
            return out.summary, out.buffer, out.n

        # the replication check rejects the engine's auto-flush cond
        # (replicated-vs-varying branch mismatch); bitwise-equivalence
        # tests against the single-host engine guard correctness instead
        smap_ingest = jax.shard_map(
            shard_ingest, mesh=self.mesh,
            in_specs=state_specs + (P(), spec1),
            out_specs=state_specs, check_vma=False)

        depth = self.config.engine.buffer_depth
        chunk = self.config.engine.chunk

        def ingest_blocks(state: SketchState, blocks: jax.Array):
            summary, buffer, n = smap_ingest(
                state.summary, state.buffer, state.n, state.fill, blocks)
            # fill evolves deterministically and identically on every shard
            # (one append per chunk, reset at buffer_depth), so it is
            # reconstructed here instead of shipped through shard_map.
            # ceil-divide: the engine EMPTY-pads a partial trailing chunk
            # and still appends it, so it counts toward the cursor.
            n_chunks = -(-blocks.shape[-1] // chunk)
            fill = (state.fill + n_chunks) % depth
            return SketchState(summary=summary, buffer=buffer, fill=fill,
                               n=n)

        self._ingest_blocks_fn = jax.jit(ingest_blocks)
        # donated twin for feed()'s loop (see single-shard branch)
        self._feed_ingest_fn = jax.jit(ingest_blocks, donate_argnums=(0,))
        self._ingest_programs = (self._ingest_blocks_fn, self._feed_ingest_fn)

        # the publish is two programs, so that a profile can tell the
        # exchange between chips from the work each chip does alone:
        # _lane_reduce (flush view + local lane reduce, one summary a shard,
        # left sharded), then _exchange (the mesh reduction strategy over
        # those rows; every rank ends with the global summary, each keeps
        # its own row). Together they evaluate exactly what the strategy
        # does in one pass: on a one-row stack its lane tree is the
        # identity. Rank 0's row is then taken as its device's buffer, with
        # no further program: a published summary lives on one device, so
        # the reads' kernels run there unpartitioned (Mosaic kernels
        # cannot be partitioned across a mesh).
        row_specs = Summary(spec1, spec1, spec1)

        def shard_lane_reduce(summary, buffer, n, fill):
            st = SketchState(summary=summary, buffer=buffer, fill=fill, n=n)
            return jax.tree.map(lambda a: a[None], eng._merged(st, axes=()))

        smap_lane_reduce = jax.shard_map(
            shard_lane_reduce, mesh=self.mesh,
            in_specs=state_specs + (P(),), out_specs=row_specs,
            check_vma=False)

        def shard_exchange(rows: Summary) -> Summary:
            return eng._reduce(rows, self._axes)

        smap_exchange = jax.shard_map(
            shard_exchange, mesh=self.mesh, in_specs=(row_specs,),
            out_specs=row_specs, check_vma=False)

        def _lane_reduce(state: SketchState) -> Summary:
            return smap_lane_reduce(state.summary, state.buffer, state.n,
                                    state.fill)

        def _exchange(rows: Summary) -> Summary:
            return smap_exchange(rows)

        self._lane_reduce_fn = jax.jit(_lane_reduce)
        self._exchange_fn = jax.jit(_exchange)
        self._merged_fn = lambda state: _rank0(self._exchange_fn(
            self._lane_reduce_fn(state)))

    # -- state construction --------------------------------------------------

    def init(self) -> SketchState:
        """A fresh sharded state: W = shards×lanes tenants on the mesh."""
        from repro.engine.state import init_state
        c = self.config.engine
        state = init_state(c.k, self.workers, c.buffer_depth, c.chunk,
                           count_dtype=c.dtype)
        if self.mesh is None:
            return state
        return jax.device_put(state, self.state_shardings())

    def state_shardings(self) -> SketchState:
        """NamedShardings of the runtime state (worker dim on the mesh)."""
        if self.mesh is None:
            raise ValueError("single-shard runtime has no mesh shardings")
        row = NamedSharding(self.mesh, P(self._dim0))
        rep = NamedSharding(self.mesh, P())
        return SketchState(summary=Summary(row, row, row), buffer=row,
                           fill=rep, n=row)

    def block_sharding(self):
        """Sharding that scatters (W, per) blocks row-wise onto shards."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P(self._dim0))

    # -- ingestion -------------------------------------------------------------

    def ingest(self, state: SketchState, stream: jax.Array) -> SketchState:
        """Ingest a global (N,) stream (or pre-decomposed (W, per) blocks).

        Pre-decomposed blocks must come from the canonical decomposition:
        their per-worker length has to be a multiple of the engine chunk.
        Accepting a ragged tail here would silently EMPTY-pad it *inside*
        the pending buffer, shifting every later chunk boundary off the
        canonical single-host decomposition — the bitwise-equivalence
        contract would break without any visible error. An empty stream is
        a no-op (zero chunks appended, state returned as-is).
        """
        stream = jnp.asarray(stream)
        blocks = stream if stream.ndim == 2 else self.decompose(stream)
        if blocks.shape[0] != self.workers:
            raise ValueError(
                f"ingest: got {blocks.shape[0]} worker blocks but this "
                f"runtime decomposes over {self.workers} workers "
                f"({self.shards} shards × {self.lanes} lanes); pass a flat "
                f"(N,) stream or use runtime.decompose()")
        if blocks.shape[-1] % self.config.engine.chunk:
            raise ValueError(
                f"ingest: per-worker block length {blocks.shape[-1]} is not "
                f"a multiple of the engine chunk "
                f"({self.config.engine.chunk}); decompose with "
                f"runtime.decompose() / host_blocks(), which EMPTY-pad to "
                f"chunk multiples")
        if blocks.shape[-1] == 0:
            return state
        return self._ingest_blocks_fn(state, blocks)

    def compile_ingest(self, widths) -> None:
        """Compile both ingest programs, plain and donated, for the
        (W, m·chunk) block of each width m in ``widths``: a coalesced group
        of m canonical blocks (one chunk a worker each).

        The programs are traced one by one from the shapes and shardings of
        the state and the block, and compiled side by side, one thread a
        program (XLA compiles outside the interpreter lock); nothing runs.
        The jitted programs then find them compiled. A width is compiled
        once per runtime, so every tier that shares the runtime finds it
        done.
        """
        with self._compile_lock:
            todo = sorted(set(widths) - self._compiled_widths)
            if not todo:
                return
            state = jax.eval_shape(self.init)
            if self.mesh is not None:
                state = jax.tree.map(
                    lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                      sharding=s),
                    state, self.state_shardings())
            chunk = self.config.engine.chunk
            lowered = []
            for m in todo:
                block = jax.ShapeDtypeStruct(
                    (self.workers, m * chunk), jnp.int32,
                    sharding=self.block_sharding())
                lowered += [program.lower(state, block)
                            for program in self._ingest_programs]
            with ThreadPoolExecutor(len(lowered)) as pool:
                for f in [pool.submit(lo.compile) for lo in lowered]:
                    f.result()
            self._compiled_widths.update(todo)

    def feed(self, state: SketchState, blocks) -> SketchState:
        """Double-buffered ingestion of an iterable of host stream blocks.

        Each element is one (N,)-shaped host array (numpy); it is
        decomposed on host, staged onto the mesh ``feed_depth`` transfers
        ahead of the compute, and ingested in arrival order.

        After the first step the loop threads its state through the
        DONATED ingest program: every intermediate state is exclusively
        owned here, so its (B, T, C) buffer and summary channels are
        aliased in place instead of round-tripping a copy per step — the
        staged host→device transfers overlap pure compute, not compute
        plus a state copy. The caller's ``state`` argument itself is
        never donated (the first step uses the non-donating program), so
        it stays valid after feed() returns.
        """
        import time as _time
        chunk = self.config.engine.chunk
        staged = (host_blocks(b, self.workers, chunk) for b in blocks)
        dev = DeviceFeed(staged, sharding=self.block_sharding(),
                         depth=self.config.resolved_feed_depth())
        ingest = self._ingest_blocks_fn
        # process-level obs (DESIGN.md §12): counts + per-block dispatch
        # latency (async — the cost the feed loop itself pays, not the
        # device compute it overlaps). The time-gated sample() pump gives
        # batch feeds — which own no ServingTier and hence no sampler
        # thread — the same ring-buffer histories a served tier gets
        # (DESIGN.md §14), at one history append per interval.
        reg = obs_metrics.DEFAULT
        m_blocks = reg.counter("runtime.feed.blocks")
        m_step = reg.histogram("runtime.feed.step_s")
        next_sample = _time.perf_counter() + FEED_SAMPLE_INTERVAL_S
        for block in dev:
            if block.shape[-1] == 0:    # empty host block → nothing pending
                continue
            t0 = _time.perf_counter()
            state = ingest(state, block)
            now = _time.perf_counter()
            m_step.record(now - t0)
            m_blocks.inc()
            if now >= next_sample:
                reg.sample(now)
                next_sample = now + FEED_SAMPLE_INTERVAL_S
            ingest = self._feed_ingest_fn
        return state

    # -- reads -----------------------------------------------------------------

    def merged(self, state: SketchState) -> Summary:
        """One global summary: flush view → lane reduce → mesh reduction
        (on a mesh, the lane-reduce program, then the exchange program)."""
        return self._merged_fn(state)

    def snapshot(self, state: SketchState, *, lazy: bool = False,
                 version: int | None = None, n_hint: int | None = None,
                 on_materialize=None, tracer=obs_trace.NULL,
                 on_exchange=None):
        """Publish an immutable versioned QuerySnapshot (QueryService handoff).

        Provenance carries the per-WORKER ingest counts ((W,) — the paper's
        block decomposition: which rank×lane saw how much of the stream)
        and the engine-resolved kernel. Like ``SketchEngine.snapshot``, the
        ingest buffer is only *viewed*, never flushed — ``state`` keeps
        appending afterwards.

        ``lazy=True`` defers the mesh reduction to the first reader (see
        ``SketchEngine.snapshot``); the caller owes the donation fence —
        ``state`` must never later be donated (``feed()`` donates its
        loop-internal states, so a published caller-held state is safe).

        On a mesh the exchange program launches directly inside
        ``tracer``'s ``ingest.exchange`` span, and ``on_exchange()`` is
        called once it is launched; a lazy snapshot does both when a reader
        materializes it, on that reader's thread. One shard has no exchange.
        """
        from repro.service.snapshot import publish_lazy
        if version is None:
            version = next(self._versions)
        obs_metrics.DEFAULT.counter("runtime.snapshot_publishes").inc()
        if lazy:
            c = self.engine.config
            return publish_lazy(
                lambda: self._eager_snapshot(state, version, tracer,
                                             on_exchange),
                version=version, kernel=c.resolved_kernel(), k=c.k,
                n_hint=n_hint, on_materialize=on_materialize)
        return self._eager_snapshot(state, version, tracer, on_exchange)

    def _eager_snapshot(self, state: SketchState, version: int,
                        tracer=obs_trace.NULL, on_exchange=None):
        from repro.service.snapshot import publish
        if self.mesh is None:
            summary, n = self._merged_fn(state), state.n.sum()
        else:
            rows = self._lane_reduce_fn(state)
            with tracer.span("ingest.exchange"):
                summary = _rank0(self._exchange_fn(rows))
            if on_exchange is not None:
                on_exchange()
            n = _rank0(state.n.sum())
        return publish(summary, n, state.n, version=version,
                       kernel=self.engine.config.resolved_kernel())

    def frontend(self):
        """A QueryFrontend matched to this runtime's resolved kernel."""
        from repro.service import QueryFrontend
        return QueryFrontend.for_engine(self.engine)
