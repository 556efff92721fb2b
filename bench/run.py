#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell uses is found by name from ``BENCHMARK.json``: the
configuration's file (``configs[].file``), the traffic mix
(``bench/traffic/<traffic>.json``), the cell's offered load
(``bench/cells/<workload>.json``), and each per-layer metric's reader
(``bench/metrics/<metric>.py``). With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` a profiled slice of the
window is reduced to its per-layer metrics and a ``breakdown``.

The run refuses anything but a TPU with at least the cell's chips: it
exits non-zero with no result line. Plan resolution is pinned to the
program's own defaults (``REPRO_PLAN_CACHE`` points at a directory that
holds no plan). JAX's persistent compilation cache lives in the checkout
(``repro.launch.compile_cache``), unless ``JAX_COMPILATION_CACHE_DIR``
places it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
EXIT_NO_CHIP = 1
EXIT_NO_PROGRAM = 2


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def device_check(chips: int) -> dict:
    """Platform, device kind and count as JAX reports them; anything but
    a TPU with at least ``chips`` chips is refused."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"[device] platform={info['platform']} kind={info['kind']!r} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        raise NoChip(f"no TPU chip: JAX found platform {info['platform']!r}"
                     f" ({info['count']} device(s)); this benchmark runs on"
                     f" a TPU only")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX found "
                     f"{info['count']}")
    return info


def load_cell(root: Path, name: str):
    """The workload ``name`` of ``root/BENCHMARK.json`` with its files."""
    from bench.harness import Cell
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    offered = json.loads(
        (root / "bench" / "cells" / f"{name}.json").read_text())
    cell = Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                offered=offered)
    return bench, cell


def cell_metrics(bench: dict, name: str, trace: bool) -> list[dict]:
    """The metric entries this cell reports in this kind of run."""
    def applies(m: dict) -> bool:
        return "workloads" not in m or name in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if applies(m)]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if applies(m) and m["moves"] in moved]


def metric_reader(root: Path, name: str) -> Path:
    """The reader file of a per-layer metric: ``bench/metrics/<name>.py``,
    else that of the quantity it splits (``device_idle_share.sat`` is read
    by ``device_idle_share.py``)."""
    own = root / "bench" / "metrics" / f"{name}.py"
    if own.exists() or "." not in name:
        return own
    return root / "bench" / "metrics" / f"{name.split('.')[0]}.py"


def read_layer_metric(root: Path, name: str, ctx) -> float | None:
    """One per-layer metric through its reader file."""
    return _load(metric_reader(root, name),
                 f"bench_metric_{name.replace('.', '_')}").read(ctx)


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_context(run: dict, cell, root: Path, device: dict):
    """What a per-layer metric reader gets: the reduced trace of the
    slice, the window's host numbers, the chip's peaks, and the work
    counts of ``bench/costs/<layer>.py``."""
    from types import SimpleNamespace

    from bench import trace_reduce
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    if device["kind"] not in peaks:
        raise KeyError(f"no peaks for device kind {device['kind']!r} in "
                       f"bench/peaks.json; have {sorted(peaks)}")
    path = trace_reduce.find_trace(run["trace_dir"])
    reduced = trace_reduce.reduce(path, devices=cell.chips)
    idle = [1 - d.busy_ns / (reduced.t1 - reduced.t0)
            for d in reduced.devices]
    print(f"[trace] file={path} bytes={path.stat().st_size} "
          f"slice_s={reduced.window_s:.6f} busy_s={reduced.busy_s:.6f} "
          f"slice_items={run['traced']['items1'] - run['traced']['items0']} "
          f"attribution={reduced.attribution} idle_per_device={idle}",
          flush=True)

    def cost(layer: str):
        return _load(root / "bench" / "costs" / f"{layer}.py",
                     f"bench_cost_{layer}")

    return SimpleNamespace(cell=cell, trace=reduced,
                           measured=run["measured"], traced=run["traced"],
                           peaks=peaks[device["kind"]], cost=cost)


def main(argv=None, *, root: Path = ROOT, t_start: float = T_START) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # plan resolution pinned to the program's defaults, whatever a
    # machine's cache holds: this directory is never written
    os.environ["REPRO_PLAN_CACHE"] = str(root / "bench" / "_out" / "no-plan")
    os.environ.pop("REPRO_PLAN_FILE", None)
    for p in (str(root), str(root / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    try:
        import repro.serve  # noqa: F401
        from bench import checks, harness
    except ImportError as e:
        print(f"bench/run.py: cannot import the program under src/ or the "
              f"benchmark ({e})", file=sys.stderr)
        return EXIT_NO_PROGRAM

    bench, cell = load_cell(root, args.workload)
    try:
        device = device_check(cell.chips)
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return EXIT_NO_CHIP

    run = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), root=root,
                           t_start=t_start)
    wanted = cell_metrics(bench, cell.name, bool(args.trace))
    device["memory_peak_bytes"] = run["memory_peak_bytes"]
    out = {"correct": checks.verdict(run["readings"]),
           "attempted": run["measured"]["blocks_acked"]
           + run["measured"]["reads_due"],
           "failed": run["measured"]["reads_failed"]}
    metrics = {}
    if args.trace:
        ctx = layer_context(run, cell, root, device)
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        for m in wanted:
            v = read_layer_metric(root, m["name"], ctx)
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            else:
                print(f"bench/run.py: per-layer metric {m['name']} found "
                      f"nothing to read in this trace; left out",
                      file=sys.stderr)
        out["breakdown"] = ctx.trace.breakdown()
    else:
        for m in wanted:
            v = run["measured"][m["name"]]
            if math.isfinite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    if "breakdown" in out:
        out["breakdown"] = out.pop("breakdown")
    out["checks"] = {name: {"value": v, "limit": checks.LIMITS[name]}
                     for name, v in run["readings"].items()}
    for name, c in out["checks"].items():
        print(f"check {name}={c['value']} limit={c['limit']}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
