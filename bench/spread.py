#!/usr/bin/env python3
"""Run one cell several times, each run its own process, and print the
spread of every metric: the measurement behind a bound.

    python3 bench/spread.py --workload paper-k2000-z1.1.fresh \\
        --seeds 1,2,3,4,5,6 --sets 2 [--trace-seeds 7,8,9] [--seconds 20]

Each set runs ``bench/run.py`` once per seed, with the same seeds in every
set. For each set and metric it prints the median and the spread: the
distance between the first and third quartiles of
``statistics.quantiles(values, n=4)``, as a share of the median. Then each
``--trace-seeds`` seed runs once with ``--trace 1``. Every result line goes
to ``chiprun_out/spread/<workload>.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def run_once(workload: str, seed: int, seconds: float, trace: int):
    cmd = [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    r = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       timeout=1500)
    lines = r.stdout.strip().splitlines()
    info = [ln for ln in lines if ln.startswith(("[window]", "[tails]",
                                                 "[writer]", "[warmup]",
                                                 "[trace]", "[memory]",
                                                 "[gc]"))]
    for ln in info:
        print(f"  {ln}", flush=True)
    if r.returncode != 0 or not lines:
        print(f"  run failed rc={r.returncode}: {r.stderr[-3000:]}",
              flush=True)
        return None
    return json.loads(lines[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    seconds = args.seconds or json.loads(
        (ROOT / "BENCHMARK.json").read_text())["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    out = ROOT / "chiprun_out" / "spread"
    out.mkdir(parents=True, exist_ok=True)
    log = (out / f"{args.workload}.jsonl").open("a")
    for set_no in range(args.sets if seeds else 0):
        values: dict[str, list[float]] = {}
        for seed in seeds:
            res = run_once(args.workload, seed, seconds, 0)
            log.write(json.dumps({"set": set_no, "seed": seed,
                                  "result": res}) + "\n")
            log.flush()
            if res is None:
                continue
            print(f"[run] set={set_no} seed={seed} correct={res['correct']}"
                  f" " + " ".join(f"{k}={v['value']}" for k, v in
                                  res["metrics"].items()), flush=True)
            for k, v in res["metrics"].items():
                values.setdefault(k, []).append(v["value"])
        for k, v in values.items():
            if len(v) >= 2:
                print(f"[spread] set={set_no} metric={k} runs={len(v)} "
                      f"median={statistics.median(v)} "
                      f"spread={spread(v)}", flush=True)
    for seed in (int(s) for s in args.trace_seeds.split(",") if s):
        res = run_once(args.workload, seed, seconds, 1)
        log.write(json.dumps({"trace": 1, "seed": seed, "result": res})
                  + "\n")
        log.flush()
        if res is not None:
            print(f"[traced] seed={seed} correct={res['correct']} "
                  + json.dumps(res["metrics"]) + " device="
                  + json.dumps(res["device"]), flush=True)
            print(f"[breakdown] {json.dumps(res.get('breakdown'))}",
                  flush=True)
    log.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
