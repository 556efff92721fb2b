"""Calibrated microbenchmark probes over the real dispatch surface.

The PlanService never reasons from first principles about kernel cost — it
measures the exact entry points the production stack dispatches through
(``kernels.ops.match_weights`` / ``combine_match`` / ``query`` and
``StreamRuntime.merged`` per reduction strategy) on synthetic inputs shaped
like real traffic: a well-formed distinct-id summary against a zipf-skewed
chunk histogram. Each probe compiles once, then takes the min over
``repeat`` timed runs (min, not mean: scheduling noise is strictly
additive), with one calibration rule — if a single run is slower than
``min_time`` the repeat count is cut to keep the sweep bounded.

Rows are plain dicts (JSON-ready for BENCH_plan.json):

  kernel probes     {op, impl, k, c, dtype, time_s}
  reduction probes  {strategy, p, pods, k, time_s}
  publish probes    {op: "publish", k, lanes, chunk, step_s, publish_s,
                     publish_per_step}
  pipeline probes   {op: "pipeline", knob: "feed"|"publish", ...}
"""
from __future__ import annotations

import functools
import time

import numpy as np

#: probe-input id-universe scale: ids are drawn from [0, 4·max(k, c)) so
#: the histogram side can always hold c DISTINCT ids (the grid label c is
#: the true input size in every cell) and a minority of ids hit the
#: summary — between the all-hit and all-miss extremes, like steady-state
#: zipf traffic
_ID_SCALE = 4


def timeit(fn, *args, repeat: int = 3, min_time: float = 0.25,
           sample_s: float = 2e-3, max_inner: int = 256) -> float:
    """Best-of-``repeat`` per-call wall time of a jax callable.

    Compile/warm-up is excluded, then each timed sample runs the call in a
    calibrated inner loop sized so one sample spans ~``sample_s`` — a
    single microsecond-scale dispatch is scheduling noise (observed 10×+
    swings between adjacent probe cells), and a mis-probed cell becomes a
    mis-planned kernel, so fast cells are amortized over enough calls to
    make the min-of-samples stable. Slow cells (single call ≥ min_time)
    stop after two samples to keep the sweep bounded.
    """
    import jax
    jax.block_until_ready(fn(*args))            # compile + warm caches
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    t1 = time.perf_counter() - t0               # calibration run
    inner = max(1, min(max_inner, int(sample_s / max(t1, 1e-9))))
    best = t1
    for i in range(max(1, repeat)):
        t0 = time.perf_counter()
        for _ in range(inner - 1):
            fn(*args)                           # async dispatch overlaps
        jax.block_until_ready(fn(*args))
        best = min(best, (time.perf_counter() - t0) / inner)
        if best >= min_time and i >= 1:          # slow cell: stop early
            break
    return best


def _probe_inputs(op: str, k: int, c: int, dtype, seed: int = 0):
    """Synthetic well-formed inputs for one (op, k, c) probe cell."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed + 7 * k + c)
    universe = _ID_SCALE * max(k, c)
    # a fully-occupied summary with distinct ids (the sorted merge-join's
    # contract), counts zipf-ish descending, errors a fraction of counts
    s_items = jnp.asarray(rng.choice(universe, size=k, replace=False)
                          .astype(np.int32))
    counts = np.sort(rng.zipf(1.3, size=k).astype(np.int64))[::-1]
    s_counts = jnp.asarray(np.minimum(counts, 2**28).astype(np.int32)
                           .astype(dtype))
    s_errors = jnp.asarray((np.asarray(s_counts) // 4).astype(dtype))
    if op == "query":
        queries = jnp.asarray(rng.integers(0, universe, size=c)
                              .astype(np.int32))
        return (s_items, s_counts, s_errors, queries)
    if op == "flush":
        # the window-level merge sees the RAW pending window — duplicates
        # and all (the histogram compression is part of what it does), so
        # the probe stream is zipf-skewed like real traffic, not a
        # distinct-id histogram
        window = jnp.asarray(
            np.minimum(rng.zipf(1.3, size=c), universe - 1)
            .astype(np.int32))
        return (s_items, s_counts, s_errors, window)
    # histogram side: exactly c distinct ids (combine's contract — both
    # absorb_pool and summary-vs-summary COMBINE feed distinct-id pools)
    h_items = jnp.asarray(rng.choice(universe, size=c,
                                     replace=False).astype(np.int32))
    h_weights = jnp.asarray(rng.integers(1, 100, size=h_items.shape[0])
                            .astype(np.int32).astype(dtype))
    if op == "update":
        return (s_items, h_items, h_weights)
    # COMBINE carries an error channel on the incoming side too (summary-
    # vs-summary merge); a fraction of the weight is representative
    return (s_items, h_items, h_weights,
            jnp.asarray((np.asarray(h_weights) // 4).astype(dtype)))


def probe_kernels(*, ops=("update", "combine", "query"),
                  impls=("jnp", "sorted"), ks=(256, 2048), cs=(512, 2048),
                  dtype="int32", repeat: int = 3, seed: int = 0,
                  emit=lambda *a: None) -> list[dict]:
    """Time every (op × impl × k × c) cell of the dispatch surface.

    Each cell times the JITTED wrapper (impl closed over statically) —
    every production dispatch runs under jit (engine methods, frontend
    estimators), and eager per-op dispatch overhead would both swamp the
    microsecond cells with noise and measure a path nothing ships.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    entry = {"update": kops.match_weights, "combine": kops.combine_match,
             "query": kops.query, "flush": kops.ingest_window}
    rows = []
    np_dtype = jnp.dtype(dtype)
    for op in ops:
        for k in ks:
            for c in cs:
                args = _probe_inputs(op, k, c, np_dtype, seed)
                for impl in impls:
                    fn = jax.jit(functools.partial(entry[op], impl=impl))
                    t = timeit(fn, *args, repeat=repeat)
                    rows.append({"op": op, "impl": impl, "k": int(k),
                                 "c": int(c), "dtype": str(dtype),
                                 "time_s": t})
                    emit(f"probe_{op}_{impl}_k{k}_c{c}", f"{t:.4e}")
    return rows


def probe_reductions(*, ps=(1, 2, 4), strategies=("butterfly", "allgather",
                                                  "hierarchical"),
                     k: int = 2048, lanes: int = 2, chunk: int = 2048,
                     depth: int = 4, n: int = 1 << 17, impl: str = "jnp",
                     repeat: int = 3, seed: int = 0,
                     emit=lambda *a: None) -> list[dict]:
    """Per-strategy snapshot-reduction latency at each probed axis size.

    Drives the real path — ``StreamRuntime.merged`` over an ingested
    sharded state — so the number includes the flush view + the strategy's
    collective rounds, exactly what a serving snapshot pays. ``ps`` is
    silently clipped to the available device count (the tune CLI
    bootstraps forced host devices up front, like launch.scale).
    """
    import jax

    from repro.data.synthetic import zipf_stream
    from repro.engine import EngineConfig
    from repro.runtime import RuntimeConfig, StreamRuntime

    rows = []
    ps = [p for p in ps if p <= len(jax.devices())]
    for p in ps:
        for strategy in strategies:
            pods = 2 if (strategy == "hierarchical" and p >= 4
                         and p % 2 == 0) else 1
            rt = StreamRuntime(RuntimeConfig(
                engine=EngineConfig(k=k, tenants=lanes, chunk=chunk,
                                    buffer_depth=depth, kernel=impl),
                shards=p, pods=pods, reduction=strategy))
            stream = zipf_stream(n, 1.1, seed=seed, max_id=10**6)
            state = rt.ingest(rt.init(), stream)
            t = timeit(rt.merged, state, repeat=repeat)
            rows.append({"strategy": strategy, "p": int(p), "pods": pods,
                         "k": int(k), "time_s": t})
            emit(f"probe_reduce_{strategy}_p{p}", f"{t:.4e}")
    return rows


def probe_publish(*, ks=(256, 2048), lanes: int = 4, chunk: int = 2048,
                  depth: int = 4, impl: str = "auto", repeat: int = 3,
                  seed: int = 0, emit=lambda *a: None) -> list[dict]:
    """The serving tier's write-path costs: one ingest step vs one publish.

    Per probed counter budget, times the two dispatches the IngestLoop
    alternates between on a warmed single-shard runtime — ``ingest`` of
    one canonical (W, chunk) block (the per-block step) and ``snapshot``
    (flush view + reduction + provenance: the whole price of publishing
    one ring version). Their ratio ``publish_per_step`` is what the tune
    CLI turns into a cadence: publish every ``ceil(ratio / budget)``
    blocks and snapshot overhead stays under ``budget`` of ingest
    throughput (DESIGN.md §11.3).
    """
    from repro.data.synthetic import zipf_stream
    from repro.engine import EngineConfig
    from repro.runtime import RuntimeConfig, StreamRuntime

    rows = []
    for k in ks:
        rt = StreamRuntime(RuntimeConfig(
            engine=EngineConfig(k=k, tenants=lanes, chunk=chunk,
                                buffer_depth=depth, kernel=impl),
            shards=1))
        rng_seed = seed + 13 * k
        # steady state: fill the summaries before timing, so the probe
        # sees production-shaped merges, not empty-summary fast paths
        warm = zipf_stream(4 * rt.workers * chunk, 1.1, seed=rng_seed,
                           max_id=10**6)
        state = rt.ingest(rt.init(), warm)
        block = rt.decompose(zipf_stream(rt.workers * chunk, 1.1,
                                         seed=rng_seed + 1, max_id=10**6))
        step_s = timeit(rt.ingest, state, block, repeat=repeat)
        # runtime.snapshot mints a fresh host-side version per call; only
        # the array work (merged + n reductions) is device time, which is
        # what block_until_ready inside timeit waits on
        publish_s = timeit(lambda: rt.snapshot(state).summary,
                           repeat=repeat)
        ratio = publish_s / max(step_s, 1e-12)
        rows.append({"op": "publish", "k": int(k), "lanes": int(lanes),
                     "chunk": int(chunk), "step_s": step_s,
                     "publish_s": publish_s, "publish_per_step": ratio})
        emit(f"probe_publish_k{k}", f"{publish_s:.4e}",
             f"step={step_s:.3e};ratio={ratio:.2f}")
    return rows


def probe_pipeline(*, k: int = 2048, lanes: int = 4, chunk: int = 2048,
                   depth: int = 4, impl: str = "auto",
                   feed_depths=(1, 2, 4),
                   repeat: int = 3, seed: int = 0,
                   emit=lambda *a: None) -> list[dict]:
    """The asynchronous-pipeline knobs, measured on the serving hot loop.

    Two sub-probes on one warmed single-shard runtime (DESIGN.md §13):

      knob="feed"      per-block cost of the feed() loop at each staging
                       depth (the double-buffering payoff curve) —
                       smallest depth within noise of the best wins
      knob="publish"   one eager snapshot vs one ingest step; when the
                       eager publish is a non-trivial fraction of a step
                       the plan turns on ``lazy_publish``
    """
    from repro.data.synthetic import zipf_stream
    from repro.engine import EngineConfig
    from repro.runtime import RuntimeConfig, StreamRuntime

    rt = StreamRuntime(RuntimeConfig(
        engine=EngineConfig(k=k, tenants=lanes, chunk=chunk,
                            buffer_depth=depth, kernel=impl),
        shards=1))
    warm = zipf_stream(4 * rt.workers * chunk, 1.1, seed=seed + 29,
                       max_id=10**6)
    state = rt.ingest(rt.init(), warm)

    rows = []
    n_blocks = 8
    feed_payloads = [zipf_stream(rt.workers * chunk, 1.1,
                                 seed=seed + 61 + i, max_id=10**6)
                     for i in range(n_blocks)]
    for d in sorted(set(int(d) for d in feed_depths if d >= 1)):
        frt = StreamRuntime(RuntimeConfig(
            engine=EngineConfig(k=k, tenants=lanes, chunk=chunk,
                                buffer_depth=depth, kernel=impl),
            shards=1, feed_depth=d))
        fstate = frt.ingest(frt.init(), warm)
        t = timeit(lambda: frt.feed(fstate, feed_payloads),
                   repeat=repeat) / n_blocks
        rows.append({"op": "pipeline", "knob": "feed", "depth": int(d),
                     "k": int(k), "block_s": t})
        emit(f"probe_pipeline_feed_d{d}", f"{t:.4e}")

    block = rt.decompose(zipf_stream(rt.workers * chunk, 1.1,
                                     seed=seed + 31, max_id=10**6))
    step_s = timeit(rt.ingest, state, block, repeat=repeat)
    eager_s = timeit(lambda: rt.snapshot(state).summary, repeat=repeat)
    rows.append({"op": "pipeline", "knob": "publish", "k": int(k),
                 "step_s": step_s, "eager_s": eager_s})
    emit("probe_pipeline_publish", f"{eager_s:.4e}",
         f"step={step_s:.3e}")
    return rows
