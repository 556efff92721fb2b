"""One tiny cell end to end on the CPU, with the chip check steered here:
the result line keeps the contract, and ``correct`` follows the served
summary."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench_tiny import REPO, run_tiny, tiny  # noqa: F401 (fixture)

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def test_tiny_cell_prints_the_contract_line(tiny, monkeypatch):
    root, name = tiny
    rc, last, out = run_tiny(root, name, monkeypatch=monkeypatch)
    assert rc == 0, out[-2000:]
    assert list(last) == KEYS           # `checks` comes last
    assert last["correct"] is True, last["checks"]
    assert last["failed"] == 0 and last["attempted"] > 0
    assert set(last["metrics"]) == {"ingest_items_per_s", "freshness_p50_ms",
                                    "read_p50_ms", "setup_s"}
    for m in last["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert last["device"]["platform"] == "tpu"
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in last["checks"].values())
    assert "compiles_in_window=0" in out


def test_corrupted_summary_is_not_correct(tiny, monkeypatch):
    """Every published summary reports half its counts."""
    import repro.service.snapshot as snapshot
    from repro.core.spacesaving import Summary

    real = snapshot.publish

    def halved(summary, n, shard_n, **kw):
        return real(Summary(summary.items, summary.counts // 2,
                            summary.errors // 2), n, shard_n, **kw)

    monkeypatch.setattr(snapshot, "publish", halved)
    root, name = tiny
    rc, last, out = run_tiny(root, name, monkeypatch=monkeypatch)
    assert rc == 0, out[-2000:]
    assert last["correct"] is False
    assert last["checks"]["underestimated"]["value"] > 0


def test_without_a_chip_exits_nonzero_and_names_it():
    env = {k: v for k, v in os.environ.items() if not k.startswith("XLA")}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "paper-k2000-z1.1.fresh", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU chip" in r.stderr
    assert not r.stdout.strip().endswith("}")


def test_without_the_program_exits_nonzero(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's own
    directories prints no result."""
    import shutil
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in json.loads((REPO / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("_out", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "paper-k2000-z1.1.fresh", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_traced_tiny_cell_reads_per_layer_metrics(tiny, monkeypatch):
    """The --trace 1 path with the trace's reduction steered: the
    per-layer readers run, and the line carries busy/window seconds and a
    breakdown."""
    from bench import trace_reduce as tr
    slice_ns = 1e9
    fake = tr.Reduced(t0=0.0, t1=slice_ns, devices=[tr.Device(
        index=0, busy_ns=0.5e9, busy=[(0.0, 0.5e9)],
        programs={"jit__ingest": [100, 0.4e9], "jit__merged": [12, 0.05e9],
                  "jit_run": [20, 0.01e9]},
        launched={"ingest.step": [100, 0.4e9], "ingest.publish": [24, 0.06e9],
                  "bench.read.point": [60, 0.01e9]})],
        host_spans=[("bench.read.point", 1e6 * i, 1e6 * i + 5e5)
                    for i in range(20)]
        + [("ingest.publish", 2e6 * i, 2e6 * i + 1e5) for i in range(12)])
    monkeypatch.setattr(tr, "reduce", lambda path, devices=None: fake)
    root, name = tiny
    rc, last, out = run_tiny(root, name, trace=1, monkeypatch=monkeypatch)
    assert rc == 0, out[-2000:]
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "checks"]
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert m["device_idle_share.sat"] == m["device_idle_share.fresh"] == 0.5
    assert m["publish_device_ms"] == pytest.approx(0.06e3 / 12)
    assert m["query_device_us_per_read"] == pytest.approx(0.01e6 / 20)
    ids = int(re.search(r"slice_items=(\d+)", out).group(1))
    assert ids > 0                   # the tier's items counter, not 100 runs
    assert m["ingest_roofline"] == pytest.approx(
        100 * (ids * 4 / 819e9) / 0.4)
    assert m["host_us_per_block"] > 0 and m["admission_wait_p99_ms"] >= 0
    assert m["freshness_tail_p99_ms"] > 0 and m["read_tail_p99_ms"] > 0
    assert last["device"]["busy_s"] == 0.5
    assert last["device"]["window_s"] == 1.0
    assert last["breakdown"]["device_ops"][0] == ["jit__ingest", 0.4]
