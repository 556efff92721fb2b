"""Entry-point plumbing for running on a chip: where the persistent compile
cache goes, and how ``benchmarks/run.py`` starts its child phases."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache
path = enable_compile_cache()
print("path", path)
print("config", jax.config.jax_compilation_cache_dir)
print("checkout", CHECKOUT_CACHE_DIR)
if {compile}:
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
"""


def _probe_cache(env_dir, compile_one: bool) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(ROOT / "src")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(compile=compile_one)],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return dict(line.split(" ", 1) for line in r.stdout.splitlines())


def test_compile_cache_honours_env_dir_and_writes_small_programs(tmp_path):
    where = tmp_path / "jaxcache"
    out = _probe_cache(where, compile_one=True)
    assert out["path"] == str(where)
    # a sub-second compile is still written: the min-compile-time floor
    # is lifted so the engine's short programs are cached too
    assert where.is_dir() and any(where.iterdir())


def test_compile_cache_defaults_to_fixed_checkout_dir():
    a = _probe_cache(None, compile_one=False)
    b = _probe_cache(None, compile_one=False)
    assert a["path"] == b["path"] == a["config"] == a["checkout"]
    assert Path(a["path"]) == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def _bench_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run", ROOT / "benchmarks" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_child_inherits_backend_and_reports_failure(monkeypatch,
                                                          capsys):
    run = _bench_run()
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    seen = {}

    def fake_run(cmd, *, env, **_):
        seen.update(cmd=cmd, env=env)
        return subprocess.CompletedProcess(cmd, 3, "", "boom")

    monkeypatch.setattr(run.subprocess, "run", fake_run)
    assert run._child("repro.launch.scale", "--quick") is False
    assert seen["cmd"][1:] == ["-m", "repro.launch.scale", "--quick"]
    # no backend is forced on the child: on a chip host it gets the chip
    assert "JAX_PLATFORMS" not in seen["env"]
    assert "repro.launch.scale,failed,rc=3" in capsys.readouterr().err


def test_bench_run_exits_nonzero_when_a_phase_fails(monkeypatch):
    run = _bench_run()
    monkeypatch.syspath_prepend(str(ROOT))      # main imports benchmarks.*
    monkeypatch.setattr(run, "run_serve", lambda emit, out: None)
    monkeypatch.setattr(sys, "argv", ["run.py", "--only", "serve"])
    with pytest.raises(SystemExit) as exit_:
        run.main()
    assert exit_.value.code == 1


def test_bench_serve_runs_under_the_static_plan(tmp_path):
    """The serving bench's child phase, at tiny sizes and under the static
    plan: it runs to its record, every phase bitwise equal to the
    reference, with the group width resolved to publish_every."""
    import json

    from repro.launch import bench_serve
    from repro.plan import static_plan, use_plan

    out = tmp_path / "BENCH_serve.json"
    with use_plan(static_plan()):
        rc = bench_serve.main([
            "--kernels", "jnp", "--k", "64", "--lanes", "2", "--chunk",
            "128", "--depth", "2", "--blocks", "12", "--layers", "1",
            "--readers", "1", "--qps", "20", "--pipeline-blocks", "8",
            "--out", str(out)])
        every = static_plan().publish_every
    assert rc == 0
    record = json.loads(out.read_text())
    assert record["config"]["coalesce_max"] == every
    assert record["summary"]["all_equivalent"]
    assert record["impls"]["jnp"]["pipeline"]["equivalent"]
