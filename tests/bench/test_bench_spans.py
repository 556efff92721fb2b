"""The readers of the program's loop and observer spans against values
counted by hand, and the reducer's launch attribution with a staging span
nested in a step."""
import importlib.util
from types import SimpleNamespace

import pytest

from bench_tiny import REPO  # first: puts the repo root on sys.path

from bench import run, trace_reduce

BLOCK = 16


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name.replace('.', '_')}", run.metric_reader(REPO, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def ctx(host_spans, *, t0=1000.0, t1=11000.0, blocks=4):
    r = trace_reduce.Reduced(t0=t0, t1=t1, devices=[], host_spans=host_spans)
    return SimpleNamespace(trace=r, traced={"items0": 0,
                                            "items1": blocks * BLOCK},
                           cell=SimpleNamespace(block=BLOCK))


# Slice [1000, 11000) ns. The loop thread: waits, a stage before its step,
# steps with stages nested in them; the last step and one of its stages
# cross the slice's end, the first wait its start. Other threads: the
# observers, one refresh crossing each bound, and a read.
SPANS = [
    ("ingest.wait", 0, 1500),                 # 500 inside
    ("ingest.stage", 1500, 1700),             # 200, outside any step
    ("ingest.step", 1700, 3000),              # 1300
    ("ingest.stage", 1800, 2100),             # 300, nested
    ("ingest.publish", 3000, 3500),
    ("ingest.wait", 3500, 4000),              # 500
    ("ingest.step", 4000, 6000),              # 2000
    ("ingest.stage", 4100, 4300),             # 200, nested
    ("ingest.stage", 4400, 4500),             # 100, nested
    ("ingest.wait", 6000, 10500),             # 4500
    ("ingest.step", 10500, 12000),            # 500 inside
    ("ingest.stage", 10600, 10900),           # 300, nested
    ("ingest.stage", 10950, 11200),           # 50 inside, nested
    ("ingest.stage", 11500, 11600),           # outside the slice
    ("ingest.observe.health", 500, 1200),     # 200 inside
    ("ingest.observe.drift", 1200, 1600),     # 400
    ("ingest.observe.tick", 5000, 5600),      # 600, across a step
    ("ingest.observe.health", 10800, 11500),  # 200 inside
    ("bench.read.point", 2000, 2500),
]


@pytest.mark.parametrize("name,want", [
    # (200 + 300 + 200 + 100 + 300 + 50) ns over 4 blocks
    ("ingest_stage_us_per_block", 1150 / 1e3 / 4),
    # steps (1300 + 2000 + 500) less their stages (300 + 300 + 350)
    ("ingest_step_self_us_per_block", (3800 - 950) / 1e3 / 4),
    # (500 + 500 + 4500) of 10000 ns
    ("ingest_wait_share", 0.55),
    # (200 + 400 + 600 + 200) ns = 1.4e-3 ms in 1e-5 s
    ("observer_host_ms_per_s.sat", 140.0),
    ("observer_host_ms_per_s.fresh", 140.0),
])
def test_span_readers_by_hand(name, want):
    assert reader(name)(ctx(SPANS)) == pytest.approx(want)


PARENT = [s for s in SPANS if s[0] in ("ingest.step", "ingest.publish",
                                       "bench.read.point")]


@pytest.mark.parametrize("name", [
    "ingest_stage_us_per_block", "ingest_step_self_us_per_block",
    "ingest_wait_share", "observer_host_ms_per_s.sat",
    "observer_host_ms_per_s.fresh"])
def test_span_readers_find_nothing_without_the_spans(name):
    # a program with only the step and publish spans: nothing to read,
    # and nothing raised
    assert reader(name)(ctx(PARENT)) is None


@pytest.mark.parametrize("name", ["ingest_stage_us_per_block",
                                  "ingest_step_self_us_per_block"])
def test_per_block_readers_need_blocks(name):
    assert reader(name)(ctx(SPANS, blocks=0)) is None


# One device: jit__ingest [300, 700) ns, jit_atleast_1d [750, 760). Host
# thread 1 (the loop): the slice [0, 1000), ingest.step [100, 800) with
# ingest.stage [120, 250) nested and closed before PjitFunction(_ingest) at
# 300, then ingest.wait [800, 950). Thread 2 (the health monitor):
# ingest.observe.health [200, 780) launching PjitFunction(atleast_1d) at 740.
NESTED = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 300000 duration_ps: 400000 }
    events { metadata_id: 1 offset_ps: 750000 duration_ps: 10000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 300000 duration_ps: 400000 }
    events { metadata_id: 3 offset_ps: 750000 duration_ps: 10000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "jit__ingest(5)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_atleast_1d(6)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 700000 }
    events { metadata_id: 3 offset_ps: 120000 duration_ps: 130000 }
    events { metadata_id: 4 offset_ps: 300000 duration_ps: 10000 }
    events { metadata_id: 5 offset_ps: 800000 duration_ps: 150000 } }
  lines { id: 2 name: "python3" timestamp_ns: 0
    events { metadata_id: 6 offset_ps: 200000 duration_ps: 580000 }
    events { metadata_id: 7 offset_ps: 740000 duration_ps: 5000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.slice" } }
  event_metadata { key: 2 value { id: 2 name: "ingest.step" } }
  event_metadata { key: 3 value { id: 3 name: "ingest.stage" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(_ingest)" } }
  event_metadata { key: 5 value { id: 5 name: "ingest.wait" } }
  event_metadata { key: 6 value { id: 6 name: "ingest.observe.health" } }
  event_metadata { key: 7 value { id: 7 name: "PjitFunction(atleast_1d)" } }
}
"""


@pytest.fixture(scope="module")
def nested():
    from jax.profiler import ProfileData
    return trace_reduce.reduce_profile(ProfileData.from_text_proto(NESTED))


def test_a_stage_closed_before_the_launch_leaves_it_to_the_step(nested):
    assert nested.attribution == "name"
    assert nested.launched_by(r"^ingest\.step$") == (1, 400)
    assert nested.launched_by(r"^ingest\.stage$") == (0, 0)
    assert nested.launched_by(r"^ingest\.observe\.") == (1, 10)
    assert sorted(n for n, _, _ in nested.host_spans) == [
        "ingest.observe.health", "ingest.stage", "ingest.step",
        "ingest.wait"]


def test_readers_on_the_reduced_trace(nested):
    c = SimpleNamespace(trace=nested, traced={"items0": 0, "items1": BLOCK},
                        cell=SimpleNamespace(block=BLOCK))
    assert reader("ingest_stage_us_per_block")(c) == pytest.approx(0.13)
    assert reader("ingest_step_self_us_per_block")(c) == pytest.approx(0.57)
    assert reader("ingest_wait_share")(c) == pytest.approx(0.15)
    # 580 ns in a 1000 ns slice
    assert reader("observer_host_ms_per_s.fresh")(c) == pytest.approx(580.0)
