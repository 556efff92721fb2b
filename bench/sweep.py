#!/usr/bin/env python3
"""Find a configuration's knee: the highest offered write rate, with a
mix's read load running, at which the admission backlog stays flat.

    python3 bench/sweep.py --config paper-k2000-z1.1 --traffic fresh \\
        --rates 6e6,8e6,1e7 --seconds 8 --seed 7 [--chips 1]

One process runs the cell's own window at each rate in turn (the same
harness, stream and reads as ``bench/run.py``) and prints one table row
per rate. A rate keeps the backlog flat when the window completes at
least 97% of what it offered and the admission wait of its last tenth of
blocks stays under 50 ms. Not part of a cell's runs: the knee it finds is
written, as an absolute rate, into ``bench/cells/<cell>.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
DELIVERED_MIN = 0.97
LAST_WAIT_MAX_MS = 50.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered write rates, items/s")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)

    import os
    os.environ["REPRO_PLAN_CACHE"] = str(ROOT / "bench" / "_out" / "no-plan")
    os.environ.pop("REPRO_PLAN_FILE", None)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from bench.run import NoChip, device_check
    try:
        device_check(args.chips)
    except NoChip as e:
        print(f"bench/sweep.py: {e}", file=sys.stderr)
        return 1
    config = json.loads(
        (ROOT / "bench" / "configs" / f"{args.config}.json").read_text())
    mix = json.loads(
        (ROOT / "bench" / "traffic" / f"{args.traffic}.json").read_text())
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        cell = harness.Cell(name=f"{args.config}.{args.traffic}.sweep",
                            chips=args.chips, config=config, mix=mix,
                            offered={"write_items_per_s": rate})
        run = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                               trace=False, root=ROOT,
                               t_start=time.perf_counter())
        m = run["measured"]
        flat = (m["ingest_items_per_s"] >= DELIVERED_MIN * rate
                and m["admission_wait_last_ms"] < LAST_WAIT_MAX_MS)
        rows.append((rate, m, flat, harness.checks.verdict(run["readings"])))
        print(f"[sweep] offered={rate:.4e} "
              f"ingest={m['ingest_items_per_s']:.4e} "
              f"delivered={m['ingest_items_per_s'] / rate:.4f} "
              f"adm_p99_ms={m['admission_wait_p99_ms']:.3f} "
              f"adm_first_ms={m['admission_wait_first_ms']:.3f} "
              f"adm_last_ms={m['admission_wait_last_ms']:.3f} "
              f"fresh_p99_ms={m['freshness_p99_ms']:.3f} "
              f"read_p99_ms={m['read_p99_ms']:.3f} flat={flat} "
              f"correct={rows[-1][3]}", flush=True)
    knee = max((r for r, _, flat, _ in rows if flat), default=None)
    print(json.dumps({"config": args.config, "traffic": args.traffic,
                      "knee_items_per_s": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
