"""Autotuning CLI — probe the dispatch surface, materialize a plan, gate it.

The paper's headline empirical result is that the best parallel
configuration is architecture-dependent; this CLI is how the stack stops
guessing. It microbenchmarks the real dispatch surface (update / combine /
query kernels per impl × k × chunk, and every reduction strategy at each
probed axis size — see repro.plan.probe), fits the interpolating cost
model, materializes an immutable ExecutionPlan, and

  * writes the plan to the fingerprint-keyed plan cache, after which every
    ``'auto'`` in the process tree (EngineConfig, RuntimeConfig, ops.query,
    QueryFrontend) resolves through it;
  * writes ``BENCH_plan.json``: the raw probe timings, the chosen plan,
    the model's predicted-vs-measured error on held-out cells, and the
    check margins — so plan regressions are visible in the bench
    trajectory;
  * with ``--check``, exits nonzero unless (a) a fresh re-measurement of
    every planned kernel choice lands within ``--tolerance`` of the best
    probed impl for that cell (and therefore never beyond tolerance of the
    worst static default), and (b) the plan-resolved 'auto' engine is
    bitwise-identical to the statically-configured engine for every probed
    impl.

Reduction probes need max(--p) host devices; on CPU the CLI re-execs
itself with ``--xla_force_host_platform_device_count`` like launch.scale.

  python -m repro.launch.tune                      # full sweep + cache
  python -m repro.launch.tune --quick --check      # CI tune-smoke leg
  python -m repro.launch.tune --no-reductions --kernels jnp,sorted,pallas
"""
from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time
from pathlib import Path

# the ops probed by default: the production dispatch surface. 'combine'
# drives every engine merge (ingest flushes, histogram absorbs, reductions
# — the unified merge core), 'query' every read, and 'flush' the
# window-level merge (ops.ingest_window — the whole deferred-flush
# dispatch), where the fused megakernel competes against the
# separate-dispatch impls. 'update' (ops.match_weights) is a public kernel
# surface with no in-tree 'auto' dispatcher since the merge unification;
# probe it on demand via --ops update,combine,query,flush — its plan table
# still resolves (static fallback) for external callers.
OPS = ("combine", "query", "flush")
# 'publish' is NOT a kernel-table op: the probe times the serving tier's
# write-path pair (one ingest step vs one snapshot publish) and the plan
# records a CADENCE (publish_every / ring_depth), not an impl choice — so
# it is handled outside the kernel sweep/gate machinery below. 'pipeline'
# likewise: it measures the async-ingestion knobs (feed_depth /
# lazy_publish, DESIGN.md §13) on the serving hot loop. The coalesce width
# is not a plan knob: the serving loop groups queued blocks up to the
# publish boundary, and on the chip each larger width ingested faster.
DEFAULT_OPS = OPS + ("publish", "pipeline")
STRATEGIES = ("butterfly", "allgather", "hierarchical")

#: snapshot publishes may cost at most this fraction of ingest
#: throughput at the planned cadence (the serving tier's SLO input)
PUBLISH_BUDGET = 0.1


def _choose_publish(rows, budget: float = PUBLISH_BUDGET) -> tuple[int, int]:
    """(publish_every, ring_depth) from the measured step/publish costs.

    Cadence: publishing every ``ceil(ratio / budget)`` ingested blocks
    caps snapshot overhead at ``budget`` of ingest throughput, where
    ``ratio`` is publish-cost / step-cost at the largest probed k (the
    production-sized budget — publish cost grows with k, so the widest
    cell is the binding one). Clamped to [1, 256].

    Ring depth: a reader that pinned ``latest`` must still find it after
    the publishes that complete while its answer materializes — one
    publish takes ``ratio`` steps of device time, during which at most
    ``ceil(ratio / publish_every)`` newer versions can land. Two slots of
    slack on top of that (the in-flight publish and the pinned read),
    clamped to [2, 16].
    """
    if not rows:
        return 8, 4
    row = max(rows, key=lambda r: r["k"])
    ratio = row["publish_per_step"]
    publish_every = max(1, min(256, math.ceil(ratio / budget)))
    ring_depth = max(2, min(16, 2 + math.ceil(ratio / publish_every)))
    return publish_every, ring_depth


#: a staging depth within this fraction of the best probed cell is "as
#: good": the SMALLEST such depth wins (less device memory)
PIPELINE_SLACK = 0.02

#: lazy publishing pays off once an eager publish costs more than this
#: fraction of one ingest step (below that the deferral bookkeeping is
#: all the laziness buys)
LAZY_PUBLISH_MIN_RATIO = 0.05


def _choose_pipeline(rows) -> tuple[int, bool]:
    """(feed_depth, lazy_publish) from the pipeline probes.

    Staging depth trades device memory for overlapped transfers, so it
    takes the SMALLEST probed depth whose per-block cost is within
    ``PIPELINE_SLACK`` of the best cell. ``lazy_publish`` turns on when
    the measured eager publish is a non-trivial fraction of one ingest
    step (the deferral then removes real write-path work for every
    never-read version).
    """
    feed_depth, lazy = 2, False
    fe = {r["depth"]: r["block_s"] for r in rows if r.get("knob") == "feed"}
    if fe:
        best = min(fe.values())
        feed_depth = min(d for d, t in fe.items()
                         if t <= (1.0 + PIPELINE_SLACK) * best)
    pub = [r for r in rows if r.get("knob") == "publish"]
    if pub:
        r = pub[-1]
        lazy = r["eager_s"] > LAZY_PUBLISH_MIN_RATIO * max(r["step_s"],
                                                           1e-12)
    return int(feed_depth), bool(lazy)


def _impls_for_op(op: str, impls) -> list[str]:
    """The impl list probed/gated at one op's dispatch surface.

    The fused megakernel only exists at the window-level 'flush' surface,
    and it is ALWAYS probed there (regardless of --kernels): fused can
    only ever reach a plan through a measurement, so the flush sweep is
    where that measurement must happen — win or lose, the number lands in
    BENCH_plan.json and the plan routes around a losing fused path.
    """
    if op == "flush":
        return list(dict.fromkeys([*impls, "fused"]))
    return list(impls)


def _midpoints(ks) -> list[int]:
    """Geometric midpoints of adjacent probed budgets (held-out cells)."""
    ks = sorted(ks)
    return [int(round(math.sqrt(a * b))) for a, b in zip(ks, ks[1:])
            if int(round(math.sqrt(a * b))) not in ks]


def _choose_chunk(model, op_ks, cs) -> int:
    """The probed chunk with the lowest per-item amortized combine cost.

    The deferred-merge engine pays one combine of a c-sized pool per chunk
    window; per-item cost is time(k, c)/c under the best impl for that
    cell, evaluated at the largest probed k (the production-sized budget —
    small-k cells are launch-bound and would bias toward tiny chunks).
    """
    k_ref = max(op_ks)
    best = min(cs, key=lambda c: min(
        model.predict("combine", i, k_ref, c)
        for i in model.impls_for("combine")) / c)
    return int(best)


def _choose_query_min_batch(rows, chunk) -> int:
    """Largest probed query batch still in the launch-overhead plateau.

    Bucketing pads point-estimate batches up to this floor; padding is
    free while the kernel is launch-bound, so pick the largest probed c
    whose best-impl time is within 25% of the smallest batch's, clamped to
    [8, 256] and below the chunk. ``rows`` must be the DEDICATED
    small-batch query probes (c well below the cost-model grid, whose
    smallest chunk already sits at/above the clamp) — the plateau lives
    below the grid, and measuring it there is the whole point.
    """
    by_c: dict = {}
    for r in rows:
        if r["op"] == "query":
            t = by_c.get(r["c"])
            by_c[r["c"]] = min(t, r["time_s"]) if t is not None \
                else r["time_s"]
    if not by_c:
        return 16
    c_min = min(by_c)
    plateau = [c for c, t in by_c.items() if t <= 1.25 * by_c[c_min]]
    return int(max(8, min(256, chunk, max(plateau, default=c_min))))


def _bitwise_gate(plan, impls, emit, seed: int = 0, ops=OPS) -> dict:
    """Plan-resolved 'auto' ≡ every static impl, per op AND end-to-end.

    Two layers: each plan table ('update'/'combine'/'query') is exercised
    directly at its own dispatch surface — 'auto' under the plan against
    every forced impl on the same inputs — and the engine path (ingest →
    snapshot, which only routes through the 'combine' table) confirms the
    composition. A plan whose query or update table routed to a broken
    impl must not pass on the strength of its merges alone.
    """
    import numpy as np

    from repro.data.synthetic import zipf_stream
    from repro.engine import EngineConfig, SketchEngine
    from repro.kernels import ops as kops
    from repro.plan import use_plan
    from repro.plan.probe import _probe_inputs

    entry = {"update": kops.match_weights, "combine": kops.combine_match,
             "query": kops.query, "flush": kops.ingest_window}

    def _same(a, b):
        if a is None or b is None:
            return a is b
        return bool((np.asarray(a) == np.asarray(b)).all())

    stream = zipf_stream(20_000, 1.2, seed=seed, max_id=10**5).reshape(2, -1)

    def snap(kernel):
        eng = SketchEngine(EngineConfig(k=256, tenants=2, chunk=512,
                                        buffer_depth=2, kernel=kernel))
        return eng.snapshot(eng.ingest(eng.init(), stream))

    results = {}
    with use_plan(plan):
        import jax.numpy as jnp
        for op in ops:
            args = _probe_inputs(op, 256, 512, jnp.dtype("int32"), seed)
            ref = entry[op](*args, impl="auto")
            for impl in _impls_for_op(op, impls):
                out = entry[op](*args, impl=impl)
                key = f"{op}:{impl}"
                results[key] = all(_same(a, b) for a, b in zip(ref, out))
                emit(f"bitwise_{op}_auto_vs_{impl}",
                     str(results[key]).lower())
        ref_snap = snap("auto")
        engine_impls = (_impls_for_op("flush", impls) if "flush" in ops
                        else list(impls))
        for impl in engine_impls:
            s = snap(impl)
            same = all(_same(a, b)
                       for a, b in zip(ref_snap.summary, s.summary))
            results[f"engine:{impl}"] = same and int(ref_snap.n) == int(s.n)
            emit(f"bitwise_engine_auto_vs_{impl}",
                 str(results[f'engine:{impl}']).lower())
    return results


def resolution_timing(emit, *, reps: int = 200,
                      cache_dir: str | None = None) -> dict:
    """Time plan resolution: cold cache load + warm per-op resolve calls.

    This is the overhead every traced 'auto' dispatch pays. THE one
    implementation of the ``plan_resolution`` metric: it rides into
    BENCH_plan.json here and benchmarks/run.py imports it for its CSV, so
    the number means the same thing in both trajectories. Two layers per
    op — ``plan_resolution_<op>`` is the UN-memoized PlanService path (a
    cache stat + table lookup per call: the before picture, and the cost
    of the first dispatch), ``plan_resolution_<op>_memo`` is the
    ``kernels.ops.resolve_impl`` memo hit every subsequent dispatch
    actually pays (the after picture). ``cache_dir`` points resolution at
    a specific plan cache (the tune CLI passes its --cache-dir so the
    measurement covers the plan this run just produced, not whatever
    $REPRO_PLAN_CACHE holds).
    """
    from repro.kernels import ops as kops
    from repro.plan import active_plan, clear, resolve_impl

    prev = os.environ.get("REPRO_PLAN_CACHE")
    if cache_dir is not None:
        os.environ["REPRO_PLAN_CACHE"] = str(cache_dir)
    clear()
    try:
        t0 = time.perf_counter()
        source = active_plan().source
        cold_s = time.perf_counter() - t0
        timing = {"cold_load_s": cold_s, "source": source}
        for op in OPS:
            t0 = time.perf_counter()
            for _ in range(reps):
                resolve_impl(op, 1024)
            timing[f"resolve_{op}_s"] = (time.perf_counter() - t0) / reps
            emit(f"plan_resolution_{op}",
                 f"{timing[f'resolve_{op}_s']:.3e}", f"source={source}")
            kops.resolve_impl(op, 1024)       # prime the memo
            t0 = time.perf_counter()
            for _ in range(reps):
                kops.resolve_impl(op, 1024)
            timing[f"resolve_{op}_memo_s"] = \
                (time.perf_counter() - t0) / reps
            emit(f"plan_resolution_{op}_memo",
                 f"{timing[f'resolve_{op}_memo_s']:.3e}",
                 f"source={source}")
        emit("plan_resolution_cold_load", f"{cold_s:.3e}")
    finally:
        if cache_dir is not None:
            if prev is None:
                os.environ.pop("REPRO_PLAN_CACHE", None)
            else:
                os.environ["REPRO_PLAN_CACHE"] = prev
            clear()
    return timing


def _bootstrap_devices(max_p: int, argv) -> int | None:
    """Re-exec with enough forced host devices for reduction probes."""
    import jax
    if (len(jax.devices()) >= max_p or jax.default_backend() != "cpu"
            or os.environ.get("REPRO_TUNE_CHILD")):
        return None
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={max_p}"
                        ).strip()
    env["REPRO_TUNE_CHILD"] = "1"
    env.setdefault("JAX_PLATFORMS", "cpu")
    print(f"[tune] re-exec with {max_p} forced host devices", flush=True)
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.tune", *argv], env=env
    ).returncode


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default=",".join(DEFAULT_OPS))
    ap.add_argument("--kernels", default="jnp,sorted",
                    help="comma list of impls to probe (pallas runs in "
                         "interpret mode off-TPU: slow, probe deliberately)")
    ap.add_argument("--k", default=None,
                    help="comma list of counter budgets to probe "
                         "(default 256,1024,4096; quick 64,256,1024)")
    ap.add_argument("--chunks", default=None,
                    help="comma list of chunk/batch sizes to probe "
                         "(default 512,2048,8192; quick 256,1024)")
    ap.add_argument("--p", default=None,
                    help="comma list of reduction axis sizes to probe "
                         "(default 1,2,4; quick 1,2)")
    ap.add_argument("--strategies", default=",".join(STRATEGIES))
    ap.add_argument("--lanes", type=int, default=2)
    ap.add_argument("--depth", type=int, default=8,
                    help="engine buffer depth recommendation carried into "
                         "the plan")
    ap.add_argument("--n-reduce", type=int, default=1 << 17,
                    help="stream length behind each reduction probe")
    ap.add_argument("--dtype", default="int32")
    ap.add_argument("--repeat", type=int, default=None,
                    help="timed runs per probe cell (default 3; quick 2)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true",
                    help="CI-smoke sizes (k≤1024, 2 chunks, p≤2)")
    ap.add_argument("--no-reductions", action="store_true",
                    help="skip reduction probes (single-device hosts)")
    ap.add_argument("--no-cache", action="store_true",
                    help="don't write the plan cache")
    ap.add_argument("--cache-dir", default=None,
                    help="plan cache directory (default: "
                         "$REPRO_PLAN_CACHE or ~/.cache/repro/plans)")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="--check: planned choice may be at most this "
                         "fraction slower than the freshly-best impl "
                         "(default 0.5; 1.0 under --quick, whose "
                         "microsecond-scale cells are dispatch-overhead "
                         "noise on shared CI runners)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless tolerance + bitwise gates hold")
    ap.add_argument("--out", default="BENCH_plan.json")
    args = ap.parse_args(argv)

    # --quick shrinks every knob the user didn't set explicitly
    q = args.quick
    args.k = args.k or ("64,256,1024" if q else "256,1024,4096")
    args.chunks = args.chunks or ("256,1024" if q else "512,2048,8192")
    args.p = args.p or ("1,2" if q else "1,2,4")
    args.repeat = args.repeat if args.repeat is not None else (2 if q else 3)
    if q:
        args.n_reduce = min(args.n_reduce, 1 << 15)
    if args.tolerance is None:
        args.tolerance = 1.0 if q else 0.5

    ops = [o.strip() for o in args.ops.split(",")]
    # the kernel-table machinery (sweep, cost model, tolerance + bitwise
    # gates) only understands impl-choice ops; 'publish' (cadence) and
    # 'pipeline' (async-ingestion knobs) are handled in their own
    # sections below
    kernel_ops = [o for o in ops if o not in ("publish", "pipeline")]
    impls = [i.strip() for i in args.kernels.split(",")]
    ks = sorted({int(k) for k in args.k.split(",")})
    cs = sorted({int(c) for c in args.chunks.split(",")})
    ps = sorted({int(p) for p in args.p.split(",")})
    strategies = [s.strip() for s in args.strategies.split(",")]

    if not args.no_reductions:
        rc = _bootstrap_devices(max(ps), argv)
        if rc is not None:
            return rc

    import jax

    from repro.plan import CostModel, ExecutionPlan, device_fingerprint, \
        plan_path, static_impl
    from repro.plan.probe import probe_kernels, probe_pipeline, \
        probe_publish, probe_reductions, timeit

    print("name,value,derived")

    def emit(name, value, derived=""):
        print(f"{name},{value},{derived}", flush=True)

    fp = device_fingerprint()
    emit("fingerprint", fp)

    # -- probe + model -------------------------------------------------------
    # per-op sweeps: the flush surface always probes the fused megakernel
    # on top of --kernels (see _impls_for_op)
    rows = []
    for op in kernel_ops:
        rows += probe_kernels(ops=(op,), impls=_impls_for_op(op, impls),
                              ks=ks, cs=cs, dtype=args.dtype,
                              repeat=args.repeat, seed=args.seed, emit=emit)
    # production queries run at small padded batches, far below the ingest
    # chunk sizes of the main grid — probe those cells too (every k, so
    # the query grid stays complete when the small columns are folded in),
    # both to site the bucket floor and to choose the query table at its
    # real operating point instead of a grid-edge clamp
    mb_rows = []
    if "query" in ops:
        mb_rows = probe_kernels(ops=("query",), impls=impls, ks=ks,
                                cs=(16, 64, 256), dtype=args.dtype,
                                repeat=args.repeat, seed=args.seed + 2)
    model = CostModel(rows + mb_rows)

    chunk = _choose_chunk(model, ks, cs) if "combine" in ops else 2048
    min_batch = _choose_query_min_batch(mb_rows, chunk)
    op_c = {"query": min_batch}
    kernels = {op: {k: model.choose_impl(op, k, op_c.get(op, chunk))
                    for k in ks} for op in kernel_ops}

    # held-out validation: probe geometric-midpoint budgets and compare
    # against the model's interpolation (the BENCH-tracked model error)
    held_out = []
    for op in kernel_ops:
        held_out += probe_kernels(ops=(op,),
                                  impls=_impls_for_op(op, impls),
                                  ks=_midpoints(ks), cs=[chunk],
                                  dtype=args.dtype, repeat=args.repeat,
                                  seed=args.seed + 1)
    validation = model.validate(held_out)
    max_err = max((v["rel_err"] for v in validation), default=0.0)
    emit("model_max_rel_err", f"{max_err:.3f}",
         f"{len(validation)} held-out cells")

    # -- reduction probes ----------------------------------------------------
    reductions, pods, reduce_rows = {}, {}, []
    if not args.no_reductions:
        impl_ref = kernels.get("combine", {}).get(
            max(ks), static_impl("combine", max(ks)))
        reduce_rows = probe_reductions(
            ps=ps, strategies=strategies, k=max(ks), lanes=args.lanes,
            chunk=chunk, depth=min(args.depth, 4), n=args.n_reduce,
            impl=impl_ref, repeat=args.repeat, seed=args.seed, emit=emit)
        by_p: dict = {}
        for r in reduce_rows:
            by_p.setdefault(r["p"], []).append(r)
        for p, cells in by_p.items():
            best = min(cells, key=lambda r: (r["time_s"], r["strategy"]))
            if p > 1:
                reductions[p] = best["strategy"]
                pods[p] = best["pods"]

    # -- publish probes (serving cadence) ------------------------------------
    # single-shard write-path pair: one ingest step vs one snapshot
    # publish, turned into the plan's publish_every/ring_depth serving
    # knobs (_choose_publish). Probed at the kernel the combine table
    # chose (the engine the serving tier actually runs).
    publish_rows = []
    publish_every, ring_depth = 8, 4
    if "publish" in ops:
        impl_pub = kernels.get("combine", {}).get(
            max(ks), static_impl("combine", max(ks)))
        publish_rows = probe_publish(
            ks=(ks if len(ks) <= 2 else (min(ks), max(ks))),
            lanes=args.lanes, chunk=chunk, depth=min(args.depth, 4),
            impl=impl_pub, repeat=args.repeat, seed=args.seed, emit=emit)
        publish_every, ring_depth = _choose_publish(publish_rows)

    # -- pipeline probes (async-ingestion knobs) -----------------------------
    # staging depth / lazy-vs-eager publish on the same single-shard
    # serving hot loop, folded into the plan's pipeline knobs
    pipeline_rows = []
    feed_depth, lazy_publish = 2, False
    if "pipeline" in ops:
        impl_pipe = kernels.get("combine", {}).get(
            max(ks), static_impl("combine", max(ks)))
        pipeline_rows = probe_pipeline(
            k=max(ks), lanes=args.lanes, chunk=chunk,
            depth=min(args.depth, 4), impl=impl_pipe,
            feed_depths=(1, 2) if q else (1, 2, 4),
            repeat=args.repeat, seed=args.seed, emit=emit)
        feed_depth, lazy_publish = _choose_pipeline(pipeline_rows)

    # -- materialize ---------------------------------------------------------
    plan = ExecutionPlan(
        fingerprint=fp, source="measured", kernels=kernels,
        reductions=reductions, pods=pods, chunk=chunk,
        buffer_depth=args.depth, query_min_batch=min_batch,
        publish_every=publish_every, ring_depth=ring_depth,
        feed_depth=feed_depth, lazy_publish=lazy_publish)
    for op in kernel_ops:
        emit(f"plan_{op}", " ".join(f"k{k}:{v}"
                                    for k, v in sorted(kernels[op].items())))
    emit("plan_chunk", chunk)
    emit("plan_query_min_batch", min_batch)
    emit("plan_publish_every", publish_every,
         f"budget={PUBLISH_BUDGET:.0%}")
    emit("plan_ring_depth", ring_depth)
    emit("plan_feed_depth", feed_depth, f"slack={PIPELINE_SLACK:.0%}")
    emit("plan_lazy_publish", str(lazy_publish).lower(),
         f"min_ratio={LAZY_PUBLISH_MIN_RATIO:.0%}")
    for p, s in sorted(reductions.items()):
        emit(f"plan_reduction_p{p}", s, f"pods={pods.get(p, 1)}")

    # -- gates ---------------------------------------------------------------
    # (a) tolerance: every impl is RE-measured at the gate cell in the same
    # pass, and the planned choice must land within --tolerance of the
    # freshly-best impl. Comparing fresh-vs-fresh (not fresh-vs-recorded)
    # cancels machine-load drift between the probe sweep and the gate —
    # and since the static default is one of the probed impls, a passing
    # gate also bounds the plan against the worst static configuration.
    gate_rows, failures = [], []
    import functools

    from repro.kernels import ops as kops
    from repro.plan.probe import _probe_inputs
    entry = {"update": kops.match_weights, "combine": kops.combine_match,
             "query": kops.query, "flush": kops.ingest_window}
    for op in kernel_ops:
        for k in ks:
            planned = kernels[op][k]
            c_cell = op_c.get(op, chunk)     # the op's real operating point
            probe_args = _probe_inputs(op, k, c_cell,
                                       jax.numpy.dtype(args.dtype),
                                       args.seed)
            # jitted, like the probe sweep — the production dispatch cost.
            # The static default is always measured alongside --kernels,
            # so the "never beyond tolerance of the worst static config"
            # bound holds even when it wasn't in the probed impl list.
            static = static_impl(op, k)
            cell_impls = list(dict.fromkeys(
                [*_impls_for_op(op, impls), static]))
            fresh = {impl: timeit(
                jax.jit(functools.partial(entry[op], impl=impl)),
                *probe_args, repeat=args.repeat)
                for impl in cell_impls}
            best = min(fresh.values())
            row = {"op": op, "k": k, "c": c_cell, "planned": planned,
                   "fresh_s": fresh, "best_fresh_s": best,
                   "static_impl": static,
                   "static_fresh_s": fresh[static],
                   "margin": fresh[planned] / best if best else 1.0}
            gate_rows.append(row)
            if fresh[planned] > (1.0 + args.tolerance) * best:
                failures.append(
                    f"{op}/k{k}: planned {planned} at {fresh[planned]:.3e}s "
                    f"exceeds best fresh impl at {best:.3e}s by more than "
                    f"{args.tolerance:.0%}")
            emit(f"gate_{op}_k{k}", f"{row['margin']:.3f}",
                 f"planned={planned};static={static}")

    # (b) bitwise: plan-resolved 'auto' ≡ every statically-configured impl,
    # at each op's dispatch surface and through the engine
    bitwise = _bitwise_gate(plan, impls, emit, seed=args.seed,
                            ops=kernel_ops)
    for key, ok in bitwise.items():
        if not ok:
            failures.append(f"bitwise: auto(plan) != static at {key}")

    # -- publish -------------------------------------------------------------
    # the cache write comes AFTER the gates: a plan that just failed its
    # own validation must never become the one every later process's
    # 'auto' silently resolves through
    cache_file = None
    if failures:
        emit("plan_cache", "skipped", f"{len(failures)} gate failure(s)")
    elif not args.no_cache:
        cache_file = plan.save(plan_path(fp, args.cache_dir))
        emit("plan_cache", str(cache_file), "written")

    timing = resolution_timing(emit, cache_dir=args.cache_dir)
    timing["file"] = str(cache_file or "")

    record = {
        "config": {
            "ops": ops, "impls": impls, "ks": ks, "cs": cs, "ps": ps,
            "strategies": strategies, "dtype": args.dtype,
            "repeat": args.repeat, "tolerance": args.tolerance,
            "backend": jax.default_backend(),
            "devices": len(jax.devices()),
        },
        "fingerprint": fp,
        "probes": rows,
        "min_batch_probes": mb_rows,
        "reduction_probes": reduce_rows,
        "publish_probes": publish_rows,
        "pipeline_probes": pipeline_rows,
        "validation": validation,
        "model_max_rel_err": max_err,
        "plan": plan.to_json(),
        "plan_cache": str(cache_file or ""),
        "check": {
            "tolerance_cells": gate_rows,
            "bitwise_equivalent": bitwise,
            "failures": failures,
        },
        "plan_resolution": timing,
    }
    Path(args.out).write_text(json.dumps(record, indent=2) + "\n")
    emit("plan_json", args.out, "written")

    if args.check:
        if failures:
            for f in failures:
                print(f"CHECK FAILED: {f}", file=sys.stderr)
            return 1
        print("check,ok,tolerance + bitwise gates hold", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
