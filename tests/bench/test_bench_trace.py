"""The trace reducer against hand-checked values: a synthetic trace built
here, and a short slice recorded on a TPU v5e."""
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401 (puts the repo root on sys.path)
from bench import trace_reduce

# Times in ns. Device 0: ops [100,300) fusion, [200,500) all-reduce,
# [700,800) fusion; module jit__ingest(3) [100,500) run 7, jit__merged(4)
# [700,800) run 8. Device 1: one op [150,250), its module run 7. Host: the
# slice [50,950), ingest.step [120,480) launching run 7 at 130,
# bench.read.point [550,900) launching run 8 at 600, and [960,990). With
# ``by="name"`` no module carries a run_id, and the launches are
# PjitFunction(_ingest) and PjitFunction(_merged) instead.
SYNTHETIC = """
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 100000 duration_ps: 200000 }}
    events {{ metadata_id: 2 offset_ps: 200000 duration_ps: 300000 }}
    events {{ metadata_id: 1 offset_ps: 700000 duration_ps: 100000 }} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 3 offset_ps: 100000 duration_ps: 400000 {s7} }}
    events {{ metadata_id: 4 offset_ps: 700000 duration_ps: 100000 {s8} }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "run_id" }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "all-reduce.3" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "jit__ingest(3)" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "jit__merged(4)" }} }}
}}
planes {{
  id: 2 name: "/device:TPU:1"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 150000 duration_ps: 100000 }} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 3 offset_ps: 150000 duration_ps: 100000 {s7} }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "run_id" }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.9" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "jit__ingest(3)" }} }}
}}
planes {{
  id: 3 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 50000 duration_ps: 900000 }}
    events {{ metadata_id: 2 offset_ps: 120000 duration_ps: 360000 }}
    events {{ metadata_id: 3 offset_ps: 550000 duration_ps: 350000 }}
    events {{ metadata_id: 3 offset_ps: 960000 duration_ps: 30000 }}
    events {{ metadata_id: 4 offset_ps: 10000 duration_ps: 5000 }}
    events {{ metadata_id: {m7} offset_ps: 130000 duration_ps: 10000
              stats {{ metadata_id: 1 int64_value: 7 }} }}
    events {{ metadata_id: {m8} offset_ps: 600000 duration_ps: 10000
              stats {{ metadata_id: 1 int64_value: 8 }} }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "run_id" }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.slice" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "ingest.step" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "bench.read.point" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "unrelated" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "Execute" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "PjitFunction(_ingest)" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "PjitFunction(_merged)" }} }}
}}
"""


RUN = "stats {{ metadata_id: 1 int64_value: {} }}"
BY = {"run_id": dict(s7=RUN.format(7), s8=RUN.format(8), m7=5, m8=5),
      "name": dict(s7="", s8="", m7=6, m8=7)}


@pytest.fixture(scope="module", params=sorted(BY))
def synthetic(request):
    from jax.profiler import ProfileData
    r = trace_reduce.reduce_profile(
        ProfileData.from_text_proto(SYNTHETIC.format(**BY[request.param])))
    assert r.attribution == request.param
    return r


def test_busy_idle_and_slice(synthetic):
    r = synthetic
    assert (r.t0, r.t1) == (50, 950)
    assert r.window_s == pytest.approx(900e-9)
    d0, d1 = r.devices
    assert d0.busy == [(100, 500), (700, 800)] and d0.busy_ns == 500
    assert d1.busy_ns == 100
    assert r.busy_s == pytest.approx(300e-9)
    assert r.idle_share() == pytest.approx(1 - 300 / 900)


def test_programs_ops_and_host_counts(synthetic):
    r = synthetic
    # averaged over two devices: (1 + 1) / 2 runs, (400 + 100) / 2 ns
    d0, d1 = r.devices
    assert d0.programs == {"jit__ingest": [1, 400.0], "jit__merged": [1, 100.0]}
    assert d1.programs == {"jit__ingest": [1, 100.0]}
    # device time by the host span that launched it (run_id 7 and 8)
    assert r.launched_by(r"^ingest\.step$") == (1.0, 250.0)
    assert r.launched_by(r"^bench\.read\.") == (0.5, 50.0)
    assert r.launched_by(r"^bench\.watch$") == (0.0, 0.0)
    assert r.host_count(r"^bench\.read\.") == 1            # one ends inside
    assert [n for n, _, _ in r.host_spans] == ["ingest.step",
                                               "bench.read.point"]


def test_gaps_named_by_open_host_spans(synthetic):
    gaps = synthetic.gaps()
    # holes in device 0: [50,100) [500,700) [800,950)
    assert [g[1] for g in gaps] == pytest.approx([200e-9, 150e-9, 50e-9])
    assert gaps[0][0].startswith("bench.read.point @0.000")
    assert gaps[1][0].startswith("bench.read.point @0.001")
    assert gaps[2][0].startswith("no host span")
    bd = synthetic.breakdown()
    assert bd["device_ops"][0] == ["jit__ingest", pytest.approx(500e-9)]
    assert len(bd["idle_gaps"]) == 3


# 20 ms recorded on the chip (see the file's header). The values below were
# checked by hand: busy time on a 1 ns timeline of the 397 op events, each
# program's executions and time summed from the module events, and the
# launches by the PjitFunction events inside each host span.
CHIP_SLICE = Path(__file__).with_name("chip_slice_v5e.txtpb")


@pytest.fixture(scope="module")
def chip():
    from jax.profiler import ProfileData
    return trace_reduce.reduce_profile(
        ProfileData.from_text_proto(CHIP_SLICE.read_text()))


def test_chip_slice_busy_and_idle(chip):
    assert (chip.t0, chip.t1) == (2_000_000, 22_000_000)
    assert chip.attribution == "name"      # no run_id on the TPU's launches
    (d,) = chip.devices
    assert d.busy_ns == 4_989_528 and len(d.busy) == 157
    assert chip.idle_share() == pytest.approx(1 - 4_989_528 / 20e6)


def test_chip_slice_programs_and_launches(chip):
    (d,) = chip.devices
    assert d.programs["jit__ingest"] == [11, 4_468_465]   # plain and donated
    assert d.programs["jit__merged"] == [1, 517_547]
    assert d.programs["jit_run"] == [2, 13_984]
    assert sum(c for c, _ in d.programs.values()) == 32
    assert chip.launched_by(r"^ingest\.step$") == (11, 4_468_465)
    # _merged, _reduce_sum, and atleast_1d, which the reads launch too
    assert chip.launched_by(r"^ingest\.publish$") == (7, 517_547 + 771 + 2744)
    # k-majority: 8 op-by-op programs; point: atleast_1d, convert, broadcast, run
    assert chip.launched_by(r"^bench\.read\.kmaj$") == (8, 6510)
    assert chip.launched_by(r"^bench\.read\.") == (19, 6510 + 17_919)
    assert chip.host_count(r"^bench\.read\.") == 3
    assert chip.host_count(r"^ingest\.publish$") == 1


def test_chip_slice_gaps(chip):
    (label, seconds), *_ = chip.gaps()
    assert label.startswith("bench.read.kmaj+bench.read.point+bench.submit+"
                            "bench.watch+ingest.step @10.168ms")
    assert seconds == pytest.approx(2_151_621e-9)
