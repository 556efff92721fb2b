"""PlanService: fingerprint, plan round-trip, resolution precedence,
threading through ops/engine/runtime/frontend, and the tune CLI."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic import zipf_stream
from repro.engine import EngineConfig, SketchEngine
from repro.plan import (ExecutionPlan, active_plan, clear, device_fingerprint,
                        plan_path, resolve_impl, resolve_reduction,
                        static_impl, static_plan, use_plan)
from repro.plan.model import CostModel
from repro.runtime import RuntimeConfig, StreamRuntime
from repro.service import QueryFrontend

K_CROSS = 256     # repro.plan.plan.SORTED_MIN_K — the static crossover


@pytest.fixture(autouse=True)
def _fresh_service():
    clear()
    yield
    clear()


def _measured(fingerprint=None, **kw):
    base = dict(
        fingerprint=fingerprint or device_fingerprint(), source="measured",
        kernels={"combine": {64: "sorted", 1024: "jnp"}},
        reductions={2: "allgather", 8: "hierarchical"}, pods={8: 2},
        chunk=1024, buffer_depth=4, query_min_batch=32)
    base.update(kw)
    return ExecutionPlan(**base)


# ---------------------------------------------------------------------------
# Plan dataclass + static fallback
# ---------------------------------------------------------------------------

def test_fingerprint_is_stable_slug():
    fp = device_fingerprint()
    assert fp == device_fingerprint()
    assert " " not in fp and fp == fp.lower()


def test_static_plan_reproduces_old_heuristics():
    plan = static_plan()
    assert plan.source == "static"
    # the former kernels/ops.py inline rules, off-TPU
    assert plan.impl_for("combine", K_CROSS - 1) == "jnp"
    assert plan.impl_for("combine", K_CROSS) == "sorted"
    assert plan.impl_for("query", 4 * K_CROSS) == "sorted"
    assert plan.impl_for("update", 4 * K_CROSS) == "jnp"   # match_weights
    assert static_impl("combine", 8192, on_tpu=True) == "pallas"
    # the former RuntimeConfig/engine reduction defaults
    assert plan.reduction_for(1) == "local"
    assert plan.reduction_for(8) == "butterfly"
    assert plan.pods_for(8) == 1


def test_static_tpu_flush_takes_the_join_from_its_crossover():
    """On TPU the flush alone moves to the two-sort join at k at or above
    the measured crossover; combine and query stay on the Pallas kernels."""
    from repro.plan import SORTJOIN_MIN_K
    assert static_impl("flush", 8000, on_tpu=True) == "sortjoin"
    assert static_impl("flush", SORTJOIN_MIN_K, on_tpu=True) == "sortjoin"
    assert static_impl("flush", SORTJOIN_MIN_K - 1, on_tpu=True) == "pallas"
    for op in ("combine", "query"):
        for k in (SORTJOIN_MIN_K - 1, 2000, 8000):
            assert static_impl(op, k, on_tpu=True) == "pallas"
    assert static_impl("flush", 8000, on_tpu=False) == "sorted"
    ExecutionPlan(fingerprint="x", source="measured",
                  kernels={"flush": {8000: "sortjoin"}}, reductions={},
                  pods={})


def test_plan_validation():
    with pytest.raises(ValueError, match="source"):
        ExecutionPlan(fingerprint="x", source="guessed", kernels={},
                      reductions={}, pods={})
    with pytest.raises(ValueError, match="unknown plan ops"):
        ExecutionPlan(fingerprint="x", source="static",
                      kernels={"merge": {}}, reductions={}, pods={})
    with pytest.raises(ValueError, match="positive"):
        ExecutionPlan(fingerprint="x", source="static", kernels={},
                      reductions={}, pods={}, chunk=0)
    # a typo'd impl in a hand-pinned plan must fail at load, not silently
    # dispatch the fall-through Pallas branch
    with pytest.raises(ValueError, match="unknown impl"):
        ExecutionPlan(fingerprint="x", source="measured",
                      kernels={"combine": {256: "srted"}}, reductions={},
                      pods={})


def test_planned_engine_config():
    from repro.plan import planned_engine_config
    cfg = planned_engine_config(k=512)       # static plan geometry
    assert (cfg.chunk, cfg.buffer_depth, cfg.kernel) == (2048, 8, "auto")
    with use_plan(_measured()):
        cfg = planned_engine_config(k=512, tenants=4)
        assert (cfg.chunk, cfg.buffer_depth, cfg.tenants) == (1024, 4, 4)
        assert planned_engine_config(k=512, chunk=256).chunk == 256


def test_plan_nearest_log_resolution():
    plan = _measured()
    # exact grid hits
    assert plan.impl_for("combine", 64) == "sorted"
    assert plan.impl_for("combine", 1024) == "jnp"
    # between grid points: nearest in log space (a log-equidistant k like
    # 256 here tie-breaks toward the smaller probed budget)
    assert plan.impl_for("combine", 128) == "sorted"
    assert plan.impl_for("combine", 512) == "jnp"
    assert plan.impl_for("combine", 256) == "sorted"
    # outside the grid clamps to the nearest edge
    assert plan.impl_for("combine", 1) == "sorted"
    assert plan.impl_for("combine", 10**6) == "jnp"
    # ops without a measured table fall back to the static rule
    assert plan.impl_for("update", 4 * K_CROSS) == "jnp"
    assert plan.reduction_for(3) == "allgather"
    assert plan.reduction_for(6) == "hierarchical"
    assert plan.pods_for(8) == 2
    assert plan.pods_for(9) == 1       # stored split must divide p


def test_plan_json_roundtrip(tmp_path):
    plan = _measured()
    assert ExecutionPlan.from_json(plan.to_json()) == plan
    path = plan.save(tmp_path / "sub" / "plan.json")
    assert ExecutionPlan.load(path) == plan
    with pytest.raises(ValueError, match="format"):
        ExecutionPlan.from_json({**plan.to_json(), "format": 99})


# ---------------------------------------------------------------------------
# Service: resolution precedence
# ---------------------------------------------------------------------------

def test_active_plan_static_by_default():
    assert active_plan().source == "static"
    assert active_plan().fingerprint == device_fingerprint()


def test_install_beats_env_and_cache(tmp_path, monkeypatch):
    fp = device_fingerprint()
    cached = _measured(chunk=512)
    cached.save(plan_path(fp, tmp_path))
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path))
    env_plan = _measured(chunk=2048)
    env_plan.save(tmp_path / "pinned.json")
    monkeypatch.setenv("REPRO_PLAN_FILE", str(tmp_path / "pinned.json"))
    clear()
    assert active_plan().chunk == 2048           # env file beats cache
    with use_plan(_measured(chunk=256)):
        assert active_plan().chunk == 256        # installed beats env
    assert active_plan().chunk == 2048
    monkeypatch.delenv("REPRO_PLAN_FILE")
    assert active_plan().chunk == 512            # cache beats static
    monkeypatch.setenv("REPRO_PLAN_CACHE",
                       str(tmp_path / "empty"))
    clear()
    assert active_plan().source == "static"


def test_pinned_plan_file_must_load(tmp_path, monkeypatch):
    # $REPRO_PLAN_FILE pins the validated configuration: a missing or
    # malformed file is a hard error, never a silent fallback
    monkeypatch.setenv("REPRO_PLAN_FILE", str(tmp_path / "nope.json"))
    with pytest.raises(ValueError, match="REPRO_PLAN_FILE"):
        active_plan()
    (tmp_path / "bad.json").write_text("{truncated")
    monkeypatch.setenv("REPRO_PLAN_FILE", str(tmp_path / "bad.json"))
    with pytest.raises(ValueError, match="REPRO_PLAN_FILE"):
        active_plan()


def test_foreign_fingerprint_cache_ignored(tmp_path, monkeypatch):
    fp = device_fingerprint()
    _measured(fingerprint="tpu-v9-jax9.9").save(plan_path(fp, tmp_path))
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path))
    clear()
    assert active_plan().source == "static"


def test_malformed_cache_falls_back(tmp_path, monkeypatch):
    plan_path(device_fingerprint(), tmp_path).parent.mkdir(
        parents=True, exist_ok=True)
    plan_path(device_fingerprint(), tmp_path).write_text("{not json")
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path))
    clear()
    assert active_plan().source == "static"


# ---------------------------------------------------------------------------
# Threading: ops / engine / runtime / frontend resolve through the plan
# ---------------------------------------------------------------------------

def test_ops_auto_routes_through_installed_plan(monkeypatch):
    from repro.kernels import ops as kops
    from repro.kernels import ref as _ref
    calls = []
    real_sorted, real_ref = _ref.combine_match_sorted, _ref.combine_match_ref
    monkeypatch.setattr(_ref, "combine_match_sorted",
                        lambda *a, **k: calls.append("sorted")
                        or real_sorted(*a, **k))
    monkeypatch.setattr(_ref, "combine_match_ref",
                        lambda *a, **k: calls.append("jnp")
                        or real_ref(*a, **k))
    s_items = jnp.arange(64, dtype=jnp.int32)
    c_items = jnp.arange(64, 80, dtype=jnp.int32)
    cnt = jnp.ones((16,), jnp.int32)
    # static fallback at k=64 → jnp; the installed plan flips it to sorted
    kops.combine_match(s_items, c_items, cnt, impl="auto")
    assert calls == ["jnp"]
    with use_plan(_measured()):
        kops.combine_match(s_items, c_items, cnt, impl="auto")
    assert calls == ["jnp", "sorted"]


def test_engine_config_resolves_through_plan():
    assert EngineConfig(k=64).resolved_kernel() == "jnp"
    assert EngineConfig(k=2048).resolved_kernel() == "sorted"
    with use_plan(_measured()):
        assert EngineConfig(k=64).resolved_kernel() == "sorted"
        assert EngineConfig(k=2048).resolved_kernel() == "jnp"
        assert EngineConfig(k=64, kernel="jnp").resolved_kernel() == "jnp"


def test_runtime_config_auto_reduction():
    rc = RuntimeConfig(engine=EngineConfig(k=64, tenants=2),
                       reduction="auto")
    assert rc.resolved_reduction(1) == "local"
    assert rc.resolved_reduction(4) == "butterfly"    # static fallback
    with use_plan(_measured()):
        assert rc.resolved_reduction(2) == "allgather"
        assert rc.resolved_reduction(8) == "hierarchical"
        assert resolve_reduction(8) == "hierarchical"
    # None still defers to the engine's declared strategy
    assert RuntimeConfig(engine=EngineConfig(k=64)).resolved_reduction(4) \
        == "local"
    with pytest.raises(ValueError, match="not registered"):
        RuntimeConfig(engine=EngineConfig(k=64), reduction="nope")


def test_runtime_builds_with_auto_reduction_and_plan_pods():
    stream = jnp.asarray(zipf_stream(8192, 1.2, seed=0, max_id=10**4))
    eng = EngineConfig(k=64, tenants=2, chunk=256, buffer_depth=2,
                       kernel="jnp")
    auto = StreamRuntime(RuntimeConfig(engine=eng, shards=1,
                                       reduction="auto", pods=None))
    explicit = StreamRuntime(RuntimeConfig(engine=eng, shards=1,
                                           reduction="local"))
    m1 = auto.merged(auto.ingest(auto.init(), stream))
    m2 = explicit.merged(explicit.ingest(explicit.init(), stream))
    for a, b in zip(m1, m2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert auto.pods == 1


def test_frontend_min_batch_from_plan():
    assert QueryFrontend("jnp").min_batch == 16      # static default
    with use_plan(_measured()):
        assert QueryFrontend("jnp").min_batch == 32
        assert QueryFrontend("jnp", min_batch=8).min_batch == 8


def test_engine_auto_bitwise_identical_to_static_impls():
    """Acceptance: planned 'auto' == statically-configured engine, per impl."""
    stream = zipf_stream(20_000, 1.2, seed=1, max_id=10**5).reshape(2, -1)

    def snap(kernel):
        eng = SketchEngine(EngineConfig(k=128, tenants=2, chunk=512,
                                        buffer_depth=2, kernel=kernel))
        return eng.snapshot(eng.ingest(eng.init(), jnp.asarray(stream)))

    for table in ({"combine": {128: "jnp"}}, {"combine": {128: "sorted"}}):
        with use_plan(_measured(kernels=table)):
            auto, fixed = snap("auto"), snap(table["combine"][128])
            other = snap("sorted" if table["combine"][128] == "jnp"
                         else "jnp")
        for a, b, c in zip(auto.summary, fixed.summary, other.summary):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        assert auto.kernel == table["combine"][128]


# ---------------------------------------------------------------------------
# Cost model
# ---------------------------------------------------------------------------

def _grid_rows(fn, ks=(64, 256, 1024), cs=(128, 512)):
    return [{"op": "combine", "impl": "jnp", "k": k, "c": c,
             "time_s": fn(k, c)} for k in ks for c in cs]


def test_cost_model_interpolates_power_laws():
    model = CostModel(_grid_rows(lambda k, c: 1e-9 * k * c))
    # exact on grid, near-exact between grid points (planar in log-log)
    assert model.predict("combine", "jnp", 256, 512) \
        == pytest.approx(1e-9 * 256 * 512, rel=1e-6)
    assert model.predict("combine", "jnp", 128, 256) \
        == pytest.approx(1e-9 * 128 * 256, rel=0.05)
    # extrapolation clamps to the probed edge
    assert model.predict("combine", "jnp", 10**6, 10**6) \
        == pytest.approx(1e-9 * 1024 * 512, rel=1e-6)


def test_cost_model_choose_and_validate():
    rows = (_grid_rows(lambda k, c: 1e-9 * k * c)
            + [{**r, "impl": "sorted", "time_s": 1e-7 * (r["k"] + r["c"])}
               for r in _grid_rows(lambda k, c: 0)])
    model = CostModel(rows)
    assert model.choose_impl("combine", 64, 128) == "jnp"
    assert model.choose_impl("combine", 1024, 512) == "sorted"
    v = model.validate([{"op": "combine", "impl": "jnp", "k": 256, "c": 512,
                         "time_s": 1e-9 * 256 * 512}])
    assert v[0]["rel_err"] == pytest.approx(0.0, abs=1e-6)
    with pytest.raises(ValueError, match="not complete"):
        CostModel(_grid_rows(lambda k, c: 1.0)[:-1])
    with pytest.raises(KeyError, match="not probed"):
        model.predict("query", "jnp", 64, 64)


# ---------------------------------------------------------------------------
# The tune CLI (in-process, tiny sizes, no reduction bootstrap)
# ---------------------------------------------------------------------------

def test_tune_cli_writes_plan_and_passes_check(tmp_path, monkeypatch):
    """What the CLI writes, and its bitwise gate. The timing-tolerance gate
    is pinned open (a planned impl may be any multiple slower): it compares
    microsecond timings that depend on how loaded the host is, not on the
    code, so it is left to the tune runs themselves."""
    from repro.launch.tune import main
    out = tmp_path / "BENCH_plan.json"
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "cache"))
    rc = main(["--check", "--no-reductions", "--tolerance", "1e9",
               "--k", "64,128", "--chunks", "128,256", "--repeat", "1",
               "--cache-dir", str(tmp_path / "cache"),
               "--out", str(out)])
    assert rc == 0
    record = json.loads(out.read_text())
    assert record["check"]["failures"] == []
    bitwise = record["check"]["bitwise_equivalent"]
    assert bitwise and all(bitwise.values())
    # every (op, k) cell of the tolerance gate was measured and recorded
    assert {(r["op"], r["k"]) for r in record["check"]["tolerance_cells"]} \
        == {(op, k) for op in ("combine", "query", "flush")
            for k in (64, 128)}
    assert {r["op"] for r in record["probes"]} \
        == {"combine", "query", "flush"}
    # the flush surface always probes the fused megakernel alongside the
    # requested --kernels; the other ops never do
    by_op = {}
    for r in record["probes"]:
        by_op.setdefault(r["op"], set()).add(r["impl"])
    assert "fused" in by_op["flush"]
    assert "fused" not in by_op["combine"] | by_op["query"]
    assert record["plan"]["source"] == "measured"
    # the cached plan is picked up by a fresh resolution pass
    cache_file = plan_path(device_fingerprint(), tmp_path / "cache")
    assert cache_file.exists()
    assert record["plan_cache"] == str(cache_file)
    clear()
    assert active_plan().source == "measured"
    assert resolve_impl("combine", 64) \
        == record["plan"]["kernels"]["combine"]["64"]
    # plan resolution overhead is recorded for the bench trajectory
    assert record["plan_resolution"]["resolve_combine_s"] > 0
