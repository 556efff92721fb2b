#!/usr/bin/env python3
"""Chip smoke test: the served Space Saving path on TPU at the paper's size.

    python chip_smoke.py              # one chip: device, kernels, serve
    python chip_smoke.py --chips 4    # four chips: the sharded runtime only

One process runs the phases in order and exits non-zero if any fails:

  device   platform, device kind and count as JAX reports them; anything
           but a TPU is refused (no CPU fallback, no interpret mode).
  kernels  ``combine_match``, ``match_weights`` and ``query`` with
           ``impl="pallas"`` against ``impl="jnp"``, bitwise, at k = 2000
           (padded to 2048), a 16384-wide window and a 512-query batch,
           with counts spanning 1 .. 2^24-1.
  serve    a ``ServingTier`` (kernel "auto", one shard) ingests the paper's
           Table I stream (``PAPER_STREAM_CONFIGS["paper-default"]``:
           k = 2000, Zipf 1.1, 10M items; 8 lanes, chunk 2048, depth 8)
           in lanes x chunk blocks while a reader thread issues point,
           top-n and k-majority reads off the snapshot ring. The drained
           snapshot must equal a synchronous ``StreamRuntime.ingest`` of
           the same blocks with kernel "jnp", bitwise, and hold the paper's
           guarantees against the exact oracle (``core/exact.py``).
  sharded  (``--chips 4`` only) the sharded ``StreamRuntime`` over four
           chips for the butterfly, allgather and hierarchical (pods = 2)
           reductions, each bitwise against the single-shard runtime over
           the same stream, with the state's shardings checked.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed. The stream is generated on the host
from ``--seed``. The persistent compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache/`` here.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

COUNT_MAX = 2**24 - 1      # largest count the kernel phase feeds
PALLAS_OPS = ("combine", "query", "flush")


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class CompileClock:
    """Process-wide backend-compile seconds and persistent-cache hits."""

    def __init__(self):
        import jax.monitoring as mon
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += secs
                self.compiles += 1

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1


# -- phases ------------------------------------------------------------------

def device_phase(min_count: int = 1) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log("device", **info)
    if info["platform"] != "tpu":
        raise RuntimeError(
            f"no TPU chip: JAX found platform {info['platform']!r}; this "
            f"smoke test runs the compiled kernels on a TPU only")
    if info["count"] < min_count:
        raise RuntimeError(f"need {min_count} TPU chips, JAX found "
                           f"{info['count']}")
    return info


def _spread_counts(rng, n: int) -> np.ndarray:
    """Log-uniform counts over [1, 2^24-1], both ends included."""
    c = np.exp(rng.uniform(0.0, np.log(COUNT_MAX), n)).astype(np.int64)
    c = np.clip(c, 1, COUNT_MAX)
    c[:2] = (1, COUNT_MAX)
    rng.shuffle(c)
    return c.astype(np.int32)


def _diff(name: str, got, want) -> list[str]:
    """Names (with mismatch counts) of the outputs that are not bitwise
    equal; empty when all are."""
    bad = []
    for i, (a, b) in enumerate(zip(got, want)):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype != b.dtype or a.shape != b.shape:
            bad.append(f"{name}[{i}] {a.dtype}{a.shape}!={b.dtype}{b.shape}")
        elif not np.array_equal(a, b):
            ne = a != b
            worst = np.abs(a.astype(np.int64) - b.astype(np.int64)).max()
            bad.append(f"{name}[{i}] {int(ne.sum())} differ, max |d|={worst}")
    return bad


def kernel_phase(*, k: int = 2000, window: int = 16384,
                 n_queries: int = 512, seed: int = 0) -> None:
    """Pallas ≡ jnp, bitwise, for the three matchers at real geometry."""
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    empty = ops.EMPTY
    ids = rng.permutation(4 * (k + window))[: k + window].astype(np.int32)
    s_items = ids[:k].copy()
    s_items[-k // 40:] = empty                      # a few free counters
    # the window shares half the monitored ids, then fresh ids, then
    # histogram padding — shuffled so matches land in every tile
    c_items = np.concatenate([s_items[: k // 2], ids[k: k + window]])
    c_items = c_items[:window]
    c_items[-window // 40:] = empty
    rng.shuffle(c_items)
    queries = np.concatenate([
        rng.choice(s_items[s_items != empty], n_queries // 2),
        ids[k + window - n_queries // 2 - n_queries // 8:
            k + window - n_queries // 8],
        np.full(n_queries // 8, empty, np.int32)])[:n_queries]
    rng.shuffle(queries)

    s_items, c_items, queries = map(jnp.asarray, (s_items, c_items, queries))
    c_counts, c_errors, s_counts, s_errors = (
        jnp.asarray(_spread_counts(rng, n))
        for n in (window, window, k, k))
    cases = {
        "combine_match": (ops.combine_match,
                          (s_items, c_items, c_counts, c_errors)),
        "match_weights": (ops.match_weights, (s_items, c_items, c_counts)),
        "query": (ops.query, (s_items, s_counts, s_errors, queries)),
    }
    bad = []
    for name, (fn, args) in cases.items():
        got = fn(*args, impl="pallas")
        want = fn(*args, impl="jnp")
        diffs = _diff(name, got, want)
        log("kernels", op=name, k=k, window=window, queries=n_queries,
            interpret=ops._interpret(), bitwise=not diffs)
        bad += diffs
    if bad:
        raise AssertionError("pallas != jnp: " + "; ".join(bad))


def _engine(k, lanes, chunk, depth, kernel):
    from repro.engine import EngineConfig
    return EngineConfig(k=k, tenants=lanes, chunk=chunk, buffer_depth=depth,
                        kernel=kernel)


def _summary_np(summary):
    return tuple(np.asarray(a) for a in summary)


def check_guarantees(items, counts, errors, stream, k: int) -> set:
    """The paper's guarantees against the exact oracle: f ≤ f̂ ≤ f + ε for
    every monitored item, and every item with f > n/k monitored. Returns
    the exact heavy-hitter ids."""
    from repro.core.exact import overestimation_violations, true_heavy_hitters
    from repro.core.spacesaving import Summary

    viol = overestimation_violations(Summary(items, counts, errors), stream)
    heavy = true_heavy_hitters(stream, k)
    missing = set(heavy) - set(items.tolist())
    log("guarantees", monitored=int((items >= 0).sum()), violations=viol,
        heavy_hitters=len(heavy), heavy_missing=len(missing))
    if viol or missing:
        raise AssertionError(f"guarantees broken: {viol} bound violations, "
                             f"{len(missing)} heavy hitters missing")
    return set(heavy)


def _reader(frontend, hot: np.ndarray, k: int, stop: threading.Event,
            out: dict) -> None:
    """Point, top-n and k-majority reads off the ring until ``stop``."""
    last = 0
    try:
        while not stop.is_set():
            est = frontend.estimate(hot)
            top = frontend.top_table(10)
            rep = frontend.k_majority_report(k)
            if not (est.lower <= est.f_hat).all():
                raise AssertionError("read with lower > f_hat")
            for v in (est.version, top.version, rep.version):
                if v < last:
                    raise AssertionError(f"version went back {last}->{v}")
                last = v
            out["reads"] += 3
    except BaseException as e:       # handed to the main thread
        out["error"] = e
    out["last_version"] = last


def serve_phase(*, k: int, skew: float, n_items: int, lanes: int = 8,
                chunk: int = 2048, depth: int = 8, seed: int = 0) -> None:
    """The served path end to end, checked bitwise and against the oracle."""
    import jax

    from repro.core.spacesaving import bounded_estimates
    from repro.data.synthetic import zipf_stream
    from repro.kernels import ops
    from repro.runtime import RuntimeConfig, StreamRuntime
    from repro.runtime.feed import host_blocks
    from repro.serve import ServeConfig, ServingTier

    routed = {op: ops.resolve_impl(op, k) for op in PALLAS_OPS}
    log("serve", k=k, routed=",".join(f"{o}:{i}" for o, i in routed.items()))
    if set(routed.values()) != {"pallas"}:
        raise AssertionError(f"'auto' does not route to pallas: {routed}")

    t0 = time.perf_counter()
    stream = zipf_stream(n_items, skew, seed=seed)
    block = lanes * chunk
    blocks = [stream[i:i + block] for i in range(0, n_items, block)]
    log("serve", items=n_items, blocks=len(blocks), block_items=block,
        gen_s=f"{time.perf_counter() - t0:.3f}")

    cfg = ServeConfig(runtime=RuntimeConfig(
        engine=_engine(k, lanes, chunk, depth, "auto"), shards=1),
        flight_recorder=False)
    runtime = StreamRuntime(cfg.runtime)
    # warm-up tier on the shared runtime: compiles ingest (plain and
    # donated), the publish reduction and the three reads
    t0 = time.perf_counter()
    with ServingTier(cfg, runtime=runtime) as warm:
        for b in blocks[:warm.publish_every + 2]:
            warm.submit(b)
        warm.drain()
        warm.frontend.estimate(stream[:64])
        warm.frontend.top_table(10)
        warm.frontend.k_majority_report(k)
    log("serve", warmup_s=f"{time.perf_counter() - t0:.3f}")

    hot = np.unique(stream[:4096])[:256]
    reads = {"reads": 0, "error": None}
    stop = threading.Event()
    tier = ServingTier(cfg, runtime=runtime).start()
    reader = threading.Thread(target=_reader, daemon=True,
                              args=(tier.frontend, hot, k, stop, reads))
    try:
        t0 = time.perf_counter()
        reader.start()
        for b in blocks:
            tier.submit(b)
        snap = tier.drain()
        tier.loop.sync()
        wall = time.perf_counter() - t0
    finally:
        stop.set()
        reader.join(timeout=120)
        tier.stop()
    if reader.is_alive():
        raise AssertionError("reader thread did not stop")
    if reads["error"] is not None:
        raise AssertionError("reader failed") from reads["error"]
    stats = tier.stats.describe()
    log("serve", wall_s=f"{wall:.3f}", items_per_s=f"{n_items / wall:.6e}",
        publishes=stats["publishes"], reads=reads["reads"],
        read_version=reads.get("last_version"), final_version=snap.version)
    if int(snap.n) != n_items or stats["items_ingested"] != n_items:
        raise AssertionError(f"ingested {int(snap.n)} of {n_items} items")

    # synchronous reference: the same blocks, kernel "jnp", no tier
    ref_rt = StreamRuntime(RuntimeConfig(
        engine=_engine(k, lanes, chunk, depth, "jnp"), shards=1))
    state = ref_rt.init()
    for b in blocks:
        state = ref_rt.ingest(state, host_blocks(b, lanes, chunk))
    served = _summary_np(snap.summary)
    ref = _summary_np(ref_rt.merged(state))
    diffs = _diff("snapshot", served, ref)
    log("serve", served_equals_sync_jnp=not diffs)
    if diffs:
        raise AssertionError("served != sync jnp: " + "; ".join(diffs))

    # point reads through the served frontend (pallas query) against the
    # jnp query on the same final snapshot
    probe = np.concatenate([served[0][served[0] >= 0], hot]).astype(np.int32)
    est = tier.frontend.estimate(probe)
    f, eps, mon = ops.query(*snap.summary, jax.numpy.asarray(probe),
                            impl="jnp")
    want = bounded_estimates(snap.summary, f, eps, mon)
    diffs = _diff("reads", (est.f_hat, est.lower, est.monitored), want)
    log("serve", reads_equal_jnp=not diffs, probed=probe.size,
        max_count=int(served[1].max()))
    if diffs:
        raise AssertionError("served reads != jnp: " + "; ".join(diffs))

    rep = tier.frontend.k_majority_report(k)
    heavy = check_guarantees(*served, stream, k)
    cand = set(rep.candidate_items.tolist())
    false_sure = set(rep.guaranteed_items.tolist()) - heavy
    log("serve", kmaj_candidates=len(cand), kmaj_guaranteed=
        rep.guaranteed_items.size, heavy_not_reported=len(heavy - cand),
        guaranteed_not_heavy=len(false_sure))
    if heavy - cand or false_sure:
        raise AssertionError("k-majority report breaks its guarantee split")


def sharded_phase(*, k: int, skew: float, n_items: int, shards: int = 4,
                  lanes: int = 8, chunk: int = 2048, depth: int = 8,
                  seed: int = 0) -> None:
    """Sharded StreamRuntime ≡ single-shard runtime, per reduction."""
    import jax

    from repro.data.synthetic import zipf_stream
    from repro.runtime import RuntimeConfig, StreamRuntime
    from repro.runtime.feed import host_blocks

    stream = zipf_stream(n_items, skew, seed=seed)
    block = shards * lanes * chunk
    blocks = [stream[i:i + block] for i in range(0, n_items, block)]
    single = StreamRuntime(RuntimeConfig(
        engine=_engine(k, shards * lanes, chunk, depth, "auto"), shards=1))
    t0 = time.perf_counter()
    ref = _summary_np(single.merged(single.feed(single.init(), blocks)))
    log("sharded", strategy="single-shard", workers=shards * lanes,
        blocks=len(blocks), kernel=single.engine.config.resolved_kernel(),
        s=f"{time.perf_counter() - t0:.3f}")

    bad = []
    for strategy, pods in (("butterfly", 1), ("allgather", 1),
                           ("hierarchical", 2)):
        rt = StreamRuntime(RuntimeConfig(
            engine=_engine(k, lanes, chunk, depth, "auto"), shards=shards,
            pods=pods, reduction=strategy))
        t0 = time.perf_counter()
        state = rt.feed(rt.init(), blocks)
        snap = rt.snapshot(state)
        got = _summary_np(snap.summary)
        secs = time.perf_counter() - t0
        # every worker row must live on its own shard's device
        staged = jax.device_put(host_blocks(blocks[0], rt.workers, chunk),
                                rt.block_sharding())
        placed = {
            name: sorted((d.id, tuple(sh.data.shape))
                         for sh in a.addressable_shards
                         for d in [sh.device])
            for name, a in (("items", state.summary.items),
                            ("buffer", state.buffer), ("block", staged))}
        spread = all(len({d for d, _ in v}) == shards
                     and all(shape[0] == lanes for _, shape in v)
                     for v in placed.values())
        diffs = _diff(strategy, got, ref)
        log("sharded", strategy=strategy, pods=pods,
            devices=len(state.summary.items.sharding.device_set),
            rows_per_device=lanes, sharded_as_expected=spread,
            n=int(snap.n), bitwise_single_shard=not diffs,
            s=f"{secs:.3f}")
        if not spread:
            bad.append(f"{strategy}: placement {placed}")
        if int(snap.n) != n_items:
            bad.append(f"{strategy}: n={int(snap.n)} != {n_items}")
        bad += diffs
    if bad:
        raise AssertionError("; ".join(bad))


# -- driver ------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded-runtime phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        from repro.configs.registry import PAPER_STREAM_CONFIGS
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    paper = PAPER_STREAM_CONFIGS["paper-default"]
    size = dict(k=paper["k_counters"], skew=paper["skew"],
                n_items=paper["n_items"], seed=args.seed)

    try:
        device = device_phase(min_count=args.chips)
    except RuntimeError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    if args.chips == 4:
        phases = [("sharded", lambda: sharded_phase(shards=4, **size))]
    else:
        phases = [("kernels", lambda: kernel_phase(k=size["k"],
                                                   seed=args.seed)),
                  ("serve", lambda: serve_phase(**size))]

    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        log(name, ok=name not in failed,
            phase_s=f"{time.perf_counter() - t0:.3f}")

    import jax
    stats = jax.devices()[0].memory_stats() or {}
    log("cache", dir=cache_dir, compile_s=f"{clock.seconds:.3f}",
        compiles=clock.compiles, cache_hits=clock.cache_hits,
        peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"))
    if failed:
        print(f"chip_smoke: failed phases: {' '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
