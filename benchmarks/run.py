"""Benchmark harness — one function per paper table/figure.

Prints ``name,value,derived`` CSV. Paper-accuracy/scaling benches run the
real algorithms at CPU-scaled sizes; the ``sketch`` section additionally
writes BENCH_sketch.json (updates/sec for the scan / chunked /
engine-buffered paths + COMBINE latency vs k, plus the per-strategy
reduction latencies folded in from the scaling sweep); the ``scaling``
section runs the StreamRuntime scaling study (repro.launch.scale, in a
child process so it can force multiple host devices on the CPU) and writes
BENCH_scaling.json; the ``plan`` section runs the autotuner probe sweep
(repro.launch.tune --quick, also subprocess-bootstrapped) into
BENCH_plan.json and times the PlanService ``plan_resolution`` hot path;
the ``roofline`` section runs the ENGINE roofline (measured kernel
dispatch vs a bytes/ops lower bound at measured host peaks, per op ×
impl × k × chunk) into the ``roofline`` key of BENCH_sketch.json, and
summarizes the model-level dry-run artifacts (results/dryrun) if present;
the ``serve`` section runs the concurrent serving-tier load harness
(repro.launch.bench_serve --quick, subprocess) into BENCH_serve.json —
sustained updates/sec with/without concurrent readers + per-op read
latency percentiles.

  PYTHONPATH=src python -m benchmarks.run [--only fig1,sketch,scaling,...]
                                          [--quick] [--check]

``--quick`` shrinks the sketch/roofline sections to CI-smoke scale (and,
when --only is not given, restricts the run to just those two sections);
``--check`` gates the run: fused must be bitwise-identical to the unfused
paths across the state matrix, and no planned impl may regress the
measured best beyond tolerance — non-zero exit on failure. A child phase
(scaling, plan, serve) that fails fails the run too. Children inherit the
caller's environment and run before this process touches JAX, so on a
chip host each phase holds the chip alone.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path


def _child(module: str, *args: str) -> bool:
    """Run one ``python -m <module>`` phase in a child process.

    The child inherits the caller's environment unchanged — it lands on
    whatever backend the parent would (the chip, where there is one) —
    and runs while this process has not yet touched JAX, so the two never
    contend for a device. A non-zero exit is reported and fails the run.
    """
    env = dict(os.environ)
    src = str(Path(__file__).resolve().parent.parent / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-m", module, *args],
                       capture_output=True, text=True, env=env)
    if r.returncode != 0:
        print(f"{module},failed,rc={r.returncode} {r.stderr[-500:]!r}",
              file=sys.stderr)
    return r.returncode == 0


def run_plan(emit, out_path: str, cache_dir: str) -> dict | None:
    """The autotuner probe sweep via ``repro.launch.tune --quick``.

    Runs in a child process (the reduction probes may re-exec with forced
    host devices); writes BENCH_plan.json and surfaces the chosen plan +
    check margins in the CSV. The plan is cached into ``cache_dir`` (a
    bench-private directory, never the user's real plan cache) so
    ``bench_plan_resolution`` can time resolution of the plan THIS run
    produced.
    """
    if not _child("repro.launch.tune", "--quick", "--cache-dir", cache_dir,
                  "--out", out_path):
        return None
    record = json.loads(Path(out_path).read_text())
    for op, table in record["plan"]["kernels"].items():
        emit(f"plan_{op}",
             " ".join(f"k{k}:{v}" for k, v in sorted(
                 table.items(), key=lambda kv: int(kv[0]))))
    emit("plan_chunk", record["plan"]["chunk"])
    emit("plan_model_max_rel_err", f"{record['model_max_rel_err']:.3f}")
    emit("plan_json", out_path, "written")
    return record


def bench_plan_resolution(emit, cache_dir: str | None = None) -> dict:
    """Per-'auto' plan-resolution overhead (the PlanService hot path).

    Every traced 'auto' dispatch pays one ``resolve_impl`` call (a cache
    stat + table lookup); this keeps that overhead a tracked number
    alongside the kernel timings it gates. One shared implementation —
    ``repro.launch.tune.resolution_timing`` — so the ``plan_resolution_*``
    labels mean the same thing here and in BENCH_plan.json; ``cache_dir``
    pins resolution to the plan ``run_plan`` just cached (the emitted
    ``source=`` tells which path was actually measured).
    """
    from repro.launch.tune import resolution_timing

    return resolution_timing(emit, reps=500, cache_dir=cache_dir)


def run_scaling(emit, out_path: str) -> dict | None:
    """The paper's scaling study via ``repro.launch.scale --quick``.

    Runs in a child process because on the CPU backend the sweep needs
    several forced host devices and XLA fixes the device count when a
    process's backend initializes; the CLI bootstraps XLA_FLAGS itself.
    """
    if not _child("repro.launch.scale", "--quick", "--out", out_path):
        return None
    record = json.loads(Path(out_path).read_text())
    for cell in record["cells"]:
        if cell["mode"] != "strong":
            continue
        emit(f"scaling_{cell['strategy']}_{cell['impl']}_p{cell['p']}",
             f"{cell['total_s']:.4e}",
             f"speedup={cell['speedup']:.2f};"
             f"efficiency={cell['efficiency']:.3f}")
    emit("scaling_json", out_path, "written")
    return record


def run_serve(emit, out_path: str) -> dict | None:
    """The serving-tier load harness via ``repro.launch.bench_serve``.

    Runs in a child process (its reader threads + ingest thread deserve a
    fresh jax process, and the quick profile pins sizes); writes
    BENCH_serve.json and surfaces the headline numbers — sustained
    updates/sec with and without readers, their ratio, and per-op p50/p99
    read latency — in the CSV.
    """
    if not _child("repro.launch.bench_serve", "--quick", "--out", out_path):
        return None
    record = json.loads(Path(out_path).read_text())
    for impl, res in record["impls"].items():
        emit(f"serve_{impl}_updates_per_s",
             f"{res['loaded']['updates_per_s']:.4e}",
             f"ratio={res['ingest_ratio']:.3f};"
             f"baseline={res['baseline']['updates_per_s']:.4e}")
        for op, q in res["loaded"]["queries"].items():
            emit(f"serve_{impl}_{op}_p99", f"{q['p99_s']:.4e}",
                 f"p50={q['p50_s']:.4e};n={q['count']}")
    s = record["summary"]
    emit("serve_min_ingest_ratio", f"{s['min_ingest_ratio']:.3f}")
    emit("serve_all_equivalent", str(s["all_equivalent"]).lower())
    emit("serve_json", out_path, "written")
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig1,fig2,tab34,fig56,sketch,"
                         "scaling,plan,roofline,serve")
    ap.add_argument("--sketch-json", default="BENCH_sketch.json",
                    help="where the sketch-bench record is written")
    ap.add_argument("--scaling-json", default="BENCH_scaling.json",
                    help="where the scaling-sweep record is written")
    ap.add_argument("--plan-json", default="BENCH_plan.json",
                    help="where the tune-sweep record is written")
    ap.add_argument("--serve-json", default="BENCH_serve.json",
                    help="where the serving-tier record is written")
    ap.add_argument("--quick", action="store_true",
                    help="CI-smoke scale; without --only, restricts the "
                         "run to the sketch+roofline sections")
    ap.add_argument("--check", action="store_true",
                    help="gate: fused ≡ unfused bitwise matrix + planned "
                         "impl within tolerance of the measured best")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    if args.quick and only is None:
        only = {"sketch", "roofline"}

    print("name,value,derived")

    def emit(name, value, derived=""):
        print(f"{name},{value},{derived}", flush=True)

    # the child-process phases run first, while this process has not yet
    # touched JAX: a process that holds the chip would starve them
    failed: list[str] = []
    scaling_record = None
    scaling_attempted = only is None or "scaling" in only
    if scaling_attempted:
        scaling_record = run_scaling(emit, args.scaling_json)
        if scaling_record is None:
            failed.append("scaling")

    plan_cache = None
    if only is None or "plan" in only:
        import tempfile
        plan_cache = tempfile.mkdtemp(prefix="bench-plan-cache-")
        if run_plan(emit, args.plan_json, plan_cache) is None:
            failed.append("plan")

    if only is None or "serve" in only:
        if run_serve(emit, args.serve_json) is None:
            failed.append("serve")

    from benchmarks import paper_benches as P

    selected = {
        "fig1": P.fig1_are,
        "fig2": P.fig2_scaling,
        "tab34": P.tab34_hybrid,
        "fig56": P.fig56_formulation,
    }
    for key, fn in selected.items():
        if only and key not in only:
            continue
        fn(emit)

    if plan_cache is not None:
        bench_plan_resolution(emit, cache_dir=plan_cache)

    check_failures: list[str] = []
    roofline_record = None
    if only is None or "roofline" in only:
        from benchmarks import roofline as R

        # the engine roofline runs against the real kops dispatch, so it
        # inherits whatever plan is cached for this process (same rule as
        # production 'auto')
        roofline_record = R.engine_roofline(emit, quick=args.quick)
        if args.check:
            check_failures += R.fused_equivalence_matrix(
                quick=args.quick, emit=emit)
            check_failures += R.planned_vs_best(
                roofline_record["cells"], emit=emit)

        # model-level dry-run artifacts, when a dryrun sweep has been run
        try:
            recs = [d for d in R.load("", "single") if "skipped" not in d]
            for d in recs:
                r = d["roofline"]
                emit(f"roofline_{d['arch']}_{d['shape']}",
                     r["step_lower_bound_s"],
                     f"bottleneck={r['bottleneck']};useful="
                     f"{(d['useful_flops_ratio'] or 0):.2f}")
        except (FileNotFoundError, LookupError) as e:
            print(f"roofline_dryrun,skipped,{e}", file=sys.stderr)

    if only is None or "sketch" in only:
        record = P.bench_sketch(emit, quick=args.quick)
        # keep BENCH_sketch.json and BENCH_scaling.json consistent: the
        # per-strategy reduction latencies ride alongside combine_latency_s.
        # Fold from the on-disk record only when the scaling section was
        # deliberately skipped — after a FAILED scaling run, silently
        # pairing this run's numbers with a stale file would misrecord.
        if (scaling_record is None and not scaling_attempted
                and Path(args.scaling_json).exists()):
            scaling_record = json.loads(Path(args.scaling_json).read_text())
        if scaling_record is not None:
            record["reduction_latency_s"] = \
                scaling_record["reduction_latency_s"]
        if roofline_record is not None:
            record["roofline"] = roofline_record
        Path(args.sketch_json).write_text(json.dumps(record, indent=2) + "\n")
        print(f"sketch_json,{args.sketch_json},written", flush=True)
    elif roofline_record is not None and Path(args.sketch_json).exists():
        # roofline-only run: fold the section into the existing record
        # in place rather than dropping it on the floor
        record = json.loads(Path(args.sketch_json).read_text())
        record["roofline"] = roofline_record
        Path(args.sketch_json).write_text(json.dumps(record, indent=2) + "\n")
        print(f"sketch_json,{args.sketch_json},roofline-updated", flush=True)

    if args.check:
        if check_failures:
            for f in check_failures:
                print(f"check,FAIL,{f}", file=sys.stderr)
            sys.exit(1)
        emit("check", "ok",
             "fused-bitwise-matrix+planned-vs-best" if roofline_record
             else "no-roofline-section")
    if failed:
        print(f"phases,FAIL,{' '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
