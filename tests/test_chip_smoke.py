"""chip_smoke.py rehearsed on the CPU: its phases at tiny sizes, Pallas in
interpret mode, and its refusal to run without a chip.

The device check is the one piece left out here (the CPU is not a TPU);
every other phase runs the same code the chip run does.
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from conftest import run_distributed

SCRIPT = Path(__file__).resolve().parent.parent / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def pallas_plan():
    """Route every 'auto' to the Pallas kernels, as the static plan does on
    a TPU, so the serve phase's routing check holds on the CPU too."""
    from repro.plan import ExecutionPlan, device_fingerprint
    from repro.plan.plan import PLAN_OPS
    from repro.plan.service import use_plan
    plan = ExecutionPlan(
        fingerprint=device_fingerprint(), source="measured",
        kernels={op: {64: "pallas"} for op in PLAN_OPS},
        reductions={}, pods={})
    with use_plan(plan):
        yield plan


def test_kernel_phase_multi_tile_interpret(smoke, capsys):
    # k = 600 pads to two 512-row tiles and the window spans two column
    # tiles: the matched-partials layout of the nk > 1 case is exercised
    smoke.kernel_phase(k=600, window=1024, n_queries=128, seed=3)
    out = capsys.readouterr().out
    for op in ("combine_match", "match_weights", "query"):
        assert f"op={op} k=600" in out
    assert out.count("bitwise=True") == 3
    assert "interpret=True" in out


def test_serve_phase_tiny_stream(smoke, pallas_plan, capsys):
    smoke.serve_phase(k=96, skew=1.1, n_items=12_000, lanes=2, chunk=256,
                      depth=2, seed=1)
    out = capsys.readouterr().out
    assert "routed=combine:pallas,query:pallas,flush:pallas" in out
    assert "served_equals_sync_jnp=True" in out
    assert "reads_equal_jnp=True" in out
    assert "violations=0" in out and "heavy_missing=0" in out
    assert "heavy_not_reported=0 guaranteed_not_heavy=0" in out


def test_serve_phase_refuses_non_pallas_routing(smoke):
    # with no plan installed the CPU's static plan picks jnp/sorted, which
    # the phase must report instead of quietly serving
    with pytest.raises(AssertionError, match="does not route to pallas"):
        smoke.serve_phase(k=96, skew=1.1, n_items=1_000, lanes=2, chunk=256,
                          depth=2)


def test_guarantee_check_catches_underestimate(smoke):
    import numpy as np
    stream = np.array([5, 5, 5, 7, 7, 9], np.int32)
    items = np.array([5, 7, -1], np.int32)
    errors = np.zeros(3, np.int32)
    smoke.check_guarantees(items, np.array([3, 2, 0], np.int32), errors,
                           stream, 3)
    with pytest.raises(AssertionError, match="1 bound violations"):
        smoke.check_guarantees(items, np.array([2, 2, 0], np.int32),
                               errors, stream, 3)
    with pytest.raises(AssertionError, match="1 heavy hitters missing"):
        smoke.check_guarantees(np.array([7, 9, -1], np.int32),
                               np.array([2, 1, 0], np.int32), errors,
                               stream, 3)


def test_sharded_phase_on_four_host_devices():
    out = run_distributed(f"""
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", {str(SCRIPT)!r})
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
mod.sharded_phase(k=64, skew=1.1, n_items=20_000, shards=4, lanes=2,
                  chunk=128, depth=2, seed=2)
print("OK")
""", n_dev=4)
    assert out.count("bitwise_single_shard=True") == 3
    assert out.count("sharded_as_expected=True") == 3
    assert "OK" in out


def test_script_refuses_cpu_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, str(SCRIPT)], capture_output=True,
                       text=True, env=env, timeout=300,
                       cwd=SCRIPT.parent)
    assert r.returncode != 0
    assert "no TPU chip" in r.stderr
    for line in r.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
