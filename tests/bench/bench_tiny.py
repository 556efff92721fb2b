"""Helpers of the benchmark's tests: a tiny cell that runs on the CPU.

Its own BENCHMARK.json, configuration, mix and offered load sit in a
temporary root, driven through ``bench/run.py``'s ``main`` with the chip
check steered from the test."""
import json
import shutil
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

FAKE_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def write_tiny_root(root: Path, *, shards: int = 1) -> str:
    """Files of one tiny cell under ``root``; returns the cell's name."""
    name = f"tiny{shards}.fresh"
    cfg = json.loads((REPO / "bench" / "configs" /
                      "paper-k2000-z1.1.json").read_text())
    cfg.update(name=f"tiny{shards}", k=64, n_items=60_000, lanes=2,
               shards=shards)
    mix = json.loads((REPO / "bench" / "traffic" / "fresh.json").read_text())
    mix.update(reads_per_s=40, trace_slice_s=0.3)
    for d in ("configs", "traffic", "cells"):
        (root / "bench" / d).mkdir(parents=True, exist_ok=True)
    (root / "bench" / "configs" / f"tiny{shards}.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(mix))
    (root / "bench" / "cells" / f"{name}.json").write_text(
        json.dumps({"write_items_per_s": 150_000 * shards}))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": f"tiny{shards}", "source": "test",
                         "file": f"bench/configs/tiny{shards}.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": name, "config": f"tiny{shards}",
                           "traffic": "tiny", "chips": shards,
                           "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for d in ("metrics", "costs"):
        shutil.copytree(REPO / "bench" / d, root / "bench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "bench" / "peaks.json", root / "bench" / "peaks.json")
    return name


def run_tiny(root: Path, name: str, *, seed: int = 2**31 + 7,
             seconds: float = 1.5, trace: int = 0, monkeypatch=None):
    """``bench/run.py`` main on the tiny cell; (exit code, last line)."""
    import bench.run as run
    import repro.launch.compile_cache as cc
    if monkeypatch is not None:
        monkeypatch.setattr(run, "device_check", lambda chips: dict(FAKE_TPU))
        # the test process keeps JAX's global cache setting as it found it
        monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")
    import io
    import contextlib
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", name, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root, t_start=time.perf_counter())
    lines = buf.getvalue().strip().splitlines()
    last = json.loads(lines[-1]) if rc == 0 else None
    return rc, last, buf.getvalue()


@pytest.fixture
def tiny(tmp_path):
    return tmp_path, write_tiny_root(tmp_path)
