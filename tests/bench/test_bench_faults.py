"""A run with the timed path broken underneath reads ``correct`` false:
once for each fault a cell can have. The chip check is steered here; the
rest of the run is the benchmark's own."""
import json
import os
import subprocess
import sys

import pytest

from bench_tiny import REPO, run_tiny, tiny  # noqa: F401 (fixture)


def _break_ingest(monkeypatch, wrap):
    from repro.runtime import StreamRuntime
    real = StreamRuntime._build_programs

    def build(self):
        real(self)
        self._ingest_blocks_fn = wrap(self._ingest_blocks_fn)
        self._feed_ingest_fn = wrap(self._feed_ingest_fn)

    monkeypatch.setattr(StreamRuntime, "_build_programs", build)


def _state_unchanged(fn):
    return lambda state, blocks: state


def _half_left_out(fn):
    def ingest(state, blocks):
        half = blocks.shape[-1] // 2
        return fn(state, blocks.at[:, half:].set(-1))
    return ingest


@pytest.mark.parametrize("wrap,reading", [
    (_state_unchanged, "items_lost"), (_half_left_out, "underestimated")])
def test_broken_ingest_is_not_correct(tiny, monkeypatch, wrap, reading):
    _break_ingest(monkeypatch, wrap)
    root, name = tiny
    rc, last, out = run_tiny(root, name, monkeypatch=monkeypatch)
    assert rc == 0, out[-2000:]
    assert last["correct"] is False
    assert last["checks"][reading]["value"] > 0


def test_altered_answer_is_not_correct(tiny, monkeypatch):
    """Point answers altered where they are produced."""
    from repro.service.frontend import QueryFrontend
    real = QueryFrontend.estimate

    def altered(self, snap, queries):
        f_hat, lower, mon = real(self, snap, queries)
        return f_hat // 2, lower // 2, mon

    monkeypatch.setattr(QueryFrontend, "estimate", altered)
    root, name = tiny
    rc, last, out = run_tiny(root, name, monkeypatch=monkeypatch)
    assert rc == 0, out[-2000:]
    assert last["correct"] is False
    assert last["checks"]["reads_wrong"]["value"] > 0


def _reversed_rows(real):
    def top_table(self, snap, n=10):
        return real(self, snap, n)[::-1]
    return "top_table", top_table


def _no_guaranteed(real):
    import dataclasses

    import numpy as np

    def k_majority_report(self, snap, k_majority):
        """Every guaranteed id reported as unconfirmed instead."""
        rep = real(self, snap, k_majority)
        moved = {f"unconfirmed_{a}": np.concatenate(
            [getattr(rep, f"guaranteed_{a}"), getattr(rep, f"unconfirmed_{a}")])
            for a in ("items", "counts", "lower")}
        return dataclasses.replace(
            rep, guaranteed_items=rep.guaranteed_items[:0],
            guaranteed_counts=rep.guaranteed_counts[:0],
            guaranteed_lower=rep.guaranteed_lower[:0], **moved)
    return "k_majority_report", k_majority_report


@pytest.mark.parametrize("alter,attr", [
    (_reversed_rows, "top_table"), (_no_guaranteed, "k_majority_report")])
def test_altered_report_is_not_correct(tiny, monkeypatch, alter, attr):
    """Reports altered where they are produced, in ways that keep every
    guarantee: the top rows reversed, the guaranteed set emptied. Only the
    recomputation from the version's own summary sees them."""
    from repro.service.frontend import QueryFrontend
    name, fn = alter(getattr(QueryFrontend, attr))
    monkeypatch.setattr(QueryFrontend, name, fn)
    root, cell = tiny
    rc, last, out = run_tiny(root, cell, seconds=2.0, monkeypatch=monkeypatch)
    assert rc == 0, out[-2000:]
    assert last["correct"] is False
    assert last["checks"]["reads_mismatched"]["value"] > 0
    assert last["checks"]["reads_wrong"]["value"] == 0


FOUR_SHARDS = """
import json, sys
from pathlib import Path
sys.path[:0] = [{repo!r}, {repo!r} + "/src", {tests!r}]
from bench_tiny import run_tiny, write_tiny_root, FAKE_TPU
import bench.run as run
import repro.launch.compile_cache as cc
run.device_check = lambda chips: dict(FAKE_TPU)
cc.enable_compile_cache = lambda: "off"
root = Path({root!r})
name = write_tiny_root(root, shards=4)
out = {{}}
rc, last, _ = run_tiny(root, name, seed=3**21)
out["sound"] = last
# the exchange between chips left out: each shard publishes its own lanes
from repro.engine import reductions
reductions.register_reduction("butterfly", reductions._local, overwrite=True)
rc, last, _ = run_tiny(root, name, seed=3**21)
out["no_exchange"] = last
print(json.dumps(out))
"""


def test_exchange_left_out_on_four_shards_is_not_correct(tmp_path):
    code = FOUR_SHARDS.format(repo=str(REPO), tests=str(REPO / "tests" /
                                                        "bench"),
                              root=str(tmp_path))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=tmp_path)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"] is True, out["sound"]["checks"]
    assert out["no_exchange"]["correct"] is False
    bad = {k for k, v in out["no_exchange"]["checks"].items() if v["value"]}
    assert bad & {"underestimated", "heavy_missing"}
