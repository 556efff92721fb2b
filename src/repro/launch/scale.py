"""The paper's scaling study on StreamRuntime — strong/weak speedup curves.

Reproduces the experimental section (Fig 2 / Tab II–IV analogues) on
simulated device counts: for every (p, reduction strategy, kernel impl)
cell a sharded StreamRuntime ingests the stream (the local pass) and
produces a global snapshot (the ParallelReduction), timed separately.
Strong scaling fixes the total stream; weak scaling fixes the per-shard
stream. Speedup and efficiency are reported against the p=1 runtime of the
same (strategy, impl), and every cell is checked bitwise against the
single-host SketchEngine over the same block decomposition.

Results go to ``BENCH_scaling.json`` (and the same ``name,value,derived``
CSV as the other harnesses). ``--check`` turns violations — sharded ≠
single-host, or NaN/zero efficiency — into a nonzero exit (the CI
scaling-smoke leg).

The sweep needs max(p) host devices; on CPU it re-execs itself in a
subprocess with ``--xla_force_host_platform_device_count`` when the
current process has fewer (XLA_FLAGS must be set before jax initializes).

  python -m repro.launch.scale                       # full default sweep
  python -m repro.launch.scale --quick --check       # CI smoke
  python -m repro.launch.scale --p 1,2,4 --strategies butterfly
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

STRATEGIES = ("butterfly", "allgather", "hierarchical")


def _timeit(fn, *args, repeat=3):
    import jax
    jax.block_until_ready(fn(*args))          # compile
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _pods_for(strategy: str, p: int) -> int:
    """hierarchical exercises the two-level ("pod","data") topology when
    the shard count can split into 2 pods; every other strategy (and small
    p) runs the flat single-pod mesh."""
    return 2 if (strategy == "hierarchical" and p >= 4 and p % 2 == 0) else 1


def _single_host_snapshot(stream, *, workers, k, chunk, depth, impl):
    """The bitwise reference: one SketchEngine over all p·lanes tenants."""
    from repro.core.parallel import block_decompose
    from repro.engine import EngineConfig, SketchEngine

    eng = SketchEngine(EngineConfig(k=k, tenants=workers, chunk=chunk,
                                    buffer_depth=depth, reduction="local",
                                    kernel=impl))
    state = eng.ingest(eng.init(), block_decompose(stream, workers, chunk))
    return eng.snapshot(state)


def _snapshots_equal(a, b) -> bool:
    import numpy as np
    same = all(bool((np.asarray(x) == np.asarray(y)).all())
               for x, y in zip(a.summary, b.summary))
    return same and int(a.n) == int(b.n)


def run_sweep(*, ps, strategies, impls, n, k, lanes, chunk, depth,
              repeat=3, modes=("strong", "weak"), seed=0, max_id=10**6,
              emit=lambda *a: None) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.data.synthetic import zipf_stream
    from repro.engine import EngineConfig
    from repro.runtime import RuntimeConfig, StreamRuntime

    max_p = max(ps)
    if len(jax.devices()) < max_p:
        raise RuntimeError(
            f"scaling sweep needs {max_p} devices, have "
            f"{len(jax.devices())}; run via `python -m repro.launch.scale` "
            f"(which bootstraps XLA_FLAGS) or force the count yourself")

    n_weak_per = max(chunk * lanes, n // max_p)
    stream_strong = jnp.asarray(
        zipf_stream(n, 1.1, seed=seed, max_id=max_id))
    cells = []
    reduction_latency = {impl: {s: {} for s in strategies}
                         for impl in impls}
    ref_cache: dict = {}    # the single-host reference depends on (p, impl)
                            # only — one full-stream ingest per pair, not
                            # one per strategy

    def make_runtime(p, strategy, impl):
        return StreamRuntime(RuntimeConfig(
            engine=EngineConfig(k=k, tenants=lanes, chunk=chunk,
                                buffer_depth=depth, kernel=impl),
            shards=p, pods=_pods_for(strategy, p), reduction=strategy))

    weak_streams: dict = {}     # keyed by n_mode — same for every strategy/impl

    def weak_stream(n_mode):
        if n_mode not in weak_streams:
            weak_streams[n_mode] = jnp.asarray(zipf_stream(
                n_mode, 1.1, seed=seed + 1, max_id=max_id))
        return weak_streams[n_mode]

    for impl in impls:
        for mode in modes:
            for strategy in strategies:
                for p in ps:
                    rt = make_runtime(p, strategy, impl)
                    n_mode = n if mode == "strong" else n_weak_per * p
                    stream = (stream_strong if mode == "strong"
                              else weak_stream(n_mode))
                    blocks = rt.decompose(stream)
                    state0 = rt.init()
                    t_ingest = _timeit(rt.ingest, state0, blocks,
                                       repeat=repeat)
                    state = rt.ingest(state0, blocks)
                    t_reduce = _timeit(rt.merged, state, repeat=repeat)
                    total = t_ingest + t_reduce
                    cell = {
                        "mode": mode, "p": p,
                        "pods": _pods_for(strategy, p),
                        "strategy": strategy, "impl": impl,
                        "n": int(n_mode), "ingest_s": t_ingest,
                        "reduce_s": t_reduce, "total_s": total,
                        "items_per_s": n_mode / total,
                    }
                    if mode == "strong":
                        reduction_latency[impl][strategy][str(p)] = t_reduce
                        snap = rt.snapshot(state)
                        if (p, impl) not in ref_cache:
                            ref_cache[(p, impl)] = _single_host_snapshot(
                                stream, workers=rt.workers, k=k,
                                chunk=chunk, depth=depth, impl=impl)
                        cell["equivalent"] = _snapshots_equal(
                            snap, ref_cache[(p, impl)])
                    cells.append(cell)
                    emit(f"scale_{mode}_{strategy}_{impl}_p{p}",
                         f"{total:.4e}",
                         f"ingest={t_ingest:.3e};reduce={t_reduce:.3e}")

    # speedup/efficiency against the smallest-p cell of the same series
    # (p=1 in the default sweep; custom --p lists without 1 still get a
    # well-defined relative baseline instead of NaNs)
    p_base = min(ps)
    by_series = {}
    for c in cells:
        by_series.setdefault((c["mode"], c["strategy"], c["impl"]),
                             {})[c["p"]] = c
    for c in cells:
        base = by_series[(c["mode"], c["strategy"], c["impl"])][p_base]
        ratio = base["total_s"] / c["total_s"]
        if c["mode"] == "strong":
            c["speedup"] = ratio * p_base
            c["efficiency"] = c["speedup"] / c["p"]
        else:   # weak: per-shard work constant → the ratio IS the efficiency
            c["speedup"], c["efficiency"] = ratio * c["p"], ratio
        emit(f"scale_{c['mode']}_{c['strategy']}_{c['impl']}_p{c['p']}_eff",
             f"{c['efficiency']:.3f}", f"speedup={c['speedup']:.3f}")

    equiv = [c["equivalent"] for c in cells if "equivalent" in c]
    effs = [c["efficiency"] for c in cells]
    record = {
        "config": {
            "n_strong": int(n), "n_weak_per_shard": int(n_weak_per),
            "k": k, "lanes": lanes, "chunk": chunk, "buffer_depth": depth,
            "ps": list(ps), "strategies": list(strategies),
            "impls": list(impls), "repeat": repeat,
            "backend": jax.default_backend(),
            "devices": len(jax.devices()),
        },
        "cells": cells,
        "reduction_latency_s": reduction_latency,
        "summary": {
            # None (JSON null) when no strong cells ran — equivalence is
            # only defined for strong mode, and a weak-only sweep must not
            # read as a failed check
            "all_equivalent": all(equiv) if equiv else None,
            "min_efficiency": min(effs) if effs else float("nan"),
            "max_speedup": max(c["speedup"] for c in cells)
            if cells else float("nan"),
        },
    }
    return record


def check_record(record: dict) -> list[str]:
    """The CI gate: equivalence must hold, efficiency must be a number > 0."""
    failures = []
    for c in record["cells"]:
        tag = f"{c['mode']}/{c['strategy']}/{c['impl']}/p{c['p']}"
        if c.get("equivalent") is False:
            failures.append(f"{tag}: sharded snapshot != single-host engine")
        eff = c.get("efficiency", float("nan"))
        if not math.isfinite(eff) or eff <= 0:
            failures.append(f"{tag}: efficiency {eff!r} is NaN/zero")
    if record["summary"]["all_equivalent"] is False:
        failures.append("summary: not all strong-scaling cells equivalent")
    return failures


def _bootstrap_devices(max_p: int, argv) -> int | None:
    """Re-exec in a subprocess with enough forced host devices (CPU only).

    XLA fixes the device count at backend initialization, so a process
    that already sees fewer than max_p devices cannot widen itself.
    """
    import jax
    if (len(jax.devices()) >= max_p or jax.default_backend() != "cpu"
            or os.environ.get("REPRO_SCALE_CHILD")):
        return None
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={max_p}"
                        ).strip()
    env["REPRO_SCALE_CHILD"] = "1"
    env.setdefault("JAX_PLATFORMS", "cpu")
    print(f"[scale] re-exec with {max_p} forced host devices", flush=True)
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.scale", *argv], env=env
    ).returncode


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    ap = argparse.ArgumentParser()
    ap.add_argument("--p", default="1,2,4,8",
                    help="comma list of shard counts")
    ap.add_argument("--strategies", default=",".join(STRATEGIES))
    ap.add_argument("--kernels", default="jnp,sorted",
                    help="comma list of combine/query impls")
    ap.add_argument("--n", type=int, default=1 << 20,
                    help="total stream length (strong scaling)")
    ap.add_argument("--k", type=int, default=2048)
    ap.add_argument("--lanes", type=int, default=2,
                    help="vmapped engine lanes per shard (OpenMP level)")
    ap.add_argument("--chunk", type=int, default=2048)
    ap.add_argument("--depth", type=int, default=4,
                    help="engine buffer depth T")
    ap.add_argument("--modes", default="strong,weak")
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true",
                    help="CI-smoke sizes (n=65k, k=256, chunk=512)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless equivalence + efficiency gates hold")
    ap.add_argument("--out", default="BENCH_scaling.json")
    args = ap.parse_args(argv)

    if args.quick:
        args.n, args.k, args.chunk, args.depth = 1 << 16, 256, 512, 2
        args.repeat = 2

    ps = sorted({int(p) for p in args.p.split(",")})
    rc = _bootstrap_devices(max(ps), argv)
    if rc is not None:
        return rc

    print("name,value,derived")

    def emit(name, value, derived=""):
        print(f"{name},{value},{derived}", flush=True)

    record = run_sweep(
        ps=ps,
        strategies=[s.strip() for s in args.strategies.split(",")],
        impls=[i.strip() for i in args.kernels.split(",")],
        n=args.n, k=args.k, lanes=args.lanes, chunk=args.chunk,
        depth=args.depth, repeat=args.repeat, seed=args.seed,
        modes=tuple(m.strip() for m in args.modes.split(",")),
        emit=emit)

    Path(args.out).write_text(json.dumps(record, indent=2) + "\n")
    emit("scaling_json", args.out, "written")
    s = record["summary"]
    emit("all_equivalent", s["all_equivalent"])
    emit("min_efficiency", f"{s['min_efficiency']:.3f}")
    emit("max_speedup", f"{s['max_speedup']:.3f}")

    if args.check:
        failures = check_record(record)
        if failures:
            for f in failures:
                print(f"CHECK FAILED: {f}", file=sys.stderr)
            return 1
        print("check,ok,equivalence + efficiency gates hold", flush=True)
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
