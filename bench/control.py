#!/usr/bin/env python3
"""The control of a cell: the readings that decide ``correct``, for the
program and for the plain reference put in its place with counts one
width narrower than the configuration states.

    python3 bench/control.py --workload paper-k2000-z1.1.fresh \\
        --seeds 11,12,13 --seconds 6

For each seed, one process runs the cell's own window at its own load
(``bench/run.py``'s harness), then reads, at the final snapshot's n:

  program   the served summary and reads (what a run compares);
  ref32     the exact top-k summary at int32, the configuration's width;
  ctl16     the same at int16, wrapped: the control, which must fail.

Not part of a cell's runs; its readings set the limits in ``PERF.md``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)

    os.environ["REPRO_PLAN_CACHE"] = str(ROOT / "bench" / "_out" / "no-plan")
    os.environ.pop("REPRO_PLAN_FILE", None)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np

    from bench import checks, harness
    from bench.oracle import Oracle
    from bench.run import NoChip, device_check, load_cell
    _, cell = load_cell(ROOT, args.workload)
    try:
        device_check(cell.chips)
    except NoChip as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 1
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                               trace=False, root=ROOT,
                               t_start=time.perf_counter())
        w = run["window"]
        n, k = int(w.final.n), int(cell.config["k"])
        oracle = Oracle(w.pool.ids)
        row = {"seed": seed, "n": n, "program": run["readings"]}
        for tag, dtype in (("ref32", np.int32), ("ctl16", np.int16)):
            row[tag] = checks.check_summary(
                oracle, *checks.control_summary(oracle, n=n, k=k,
                                                dtype=dtype),
                n=n, acked=n, k=k)
        for tag in ("program", "ref32", "ctl16"):
            print(f"[control] seed={seed} n={n} {tag} "
                  + " ".join(f"{a}={b}" for a, b in row[tag].items())
                  + f" correct={checks.verdict(row[tag])}", flush=True)
        out.append(row)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
