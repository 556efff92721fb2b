"""PlanService — measurement-driven autotuning of the dispatch surface.

The subsystem behind every "auto" in the stack (DESIGN.md §9):

  * :mod:`repro.plan.fingerprint` — device fingerprint + plan-cache paths;
  * :mod:`repro.plan.probe`       — calibrated microbenchmarks of the real
    dispatch surface (match/combine/query kernels, reduction strategies);
  * :mod:`repro.plan.model`       — log-log interpolating cost model;
  * :class:`ExecutionPlan`        — the immutable, JSON-cached decision
    table (kernel impl per op × k, reduction per axis size, chunk/buffer
    geometry, query bucketing);
  * :mod:`repro.plan.service`     — resolution precedence: installed plan
    → $REPRO_PLAN_FILE → fingerprint cache → static fallback.

``python -m repro.launch.tune`` runs the probe sweep, materializes and
caches a measured plan, and writes BENCH_plan.json.
"""
from repro.plan.fingerprint import cache_dir, device_fingerprint, plan_path
from repro.plan.model import CostModel
from repro.plan.plan import (PLAN_IMPLS, PLAN_OPS, SORTED_MIN_K,
                             SORTJOIN_MIN_K, ExecutionPlan, static_impl,
                             static_plan)
from repro.plan.service import (active_plan, clear, install,
                                planned_engine_config, resolve_impl,
                                resolve_reduction, use_plan)

__all__ = [
    "PLAN_IMPLS", "PLAN_OPS", "SORTED_MIN_K", "SORTJOIN_MIN_K", "CostModel",
    "ExecutionPlan", "active_plan", "cache_dir", "clear",
    "device_fingerprint", "install", "plan_path", "planned_engine_config",
    "resolve_impl", "resolve_reduction", "static_impl", "static_plan",
    "use_plan",
]
