"""The readers of the exchange between chips against values counted by hand
on a synthetic four-chip trace; nothing to read on one chip."""
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench_tiny import REPO  # first: puts the repo root on sys.path

from bench import run, trace_reduce

MS = 1_000_000                 # ns


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", run.metric_reader(REPO, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# Times in µs, written as ps × 1e6 below. The slice [0, 10000). The loop
# thread publishes twice: ingest.publish [1000, 1400) launches _lane_reduce
# at 1050, and nests ingest.exchange [1100, 1300) launching _exchange at
# 1150; the same at 5000. A third exchange span [9900, 10100) ends past
# the slice. Each of the 4 devices runs jit__lane_reduce [1200, 1700) and
# [5200, 5700), and jit__exchange [1800, 1800 + a) and [5800, 6200) with
# a = 300, 320, 340, 360 µs on devices 0..3.
DEVICE = """
planes {{
  id: {pid} name: "/device:TPU:{i}"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 3 offset_ps: 1200000000 duration_ps: 500000000 }}
    events {{ metadata_id: 3 offset_ps: 1800000000 duration_ps: {a}000000 }}
    events {{ metadata_id: 3 offset_ps: 5200000000 duration_ps: 500000000 }}
    events {{ metadata_id: 3 offset_ps: 5800000000 duration_ps: 400000000 }} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 1200000000 duration_ps: 500000000 }}
    events {{ metadata_id: 2 offset_ps: 1800000000 duration_ps: {a}000000 }}
    events {{ metadata_id: 1 offset_ps: 5200000000 duration_ps: 500000000 }}
    events {{ metadata_id: 2 offset_ps: 5800000000 duration_ps: 400000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit__lane_reduce(4)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit__exchange(5)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "fusion.1" }} }}
}}
"""
HOST = """
planes {
  id: 9 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }
    events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 400000000 }
    events { metadata_id: 4 offset_ps: 1050000000 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 1100000000 duration_ps: 200000000 }
    events { metadata_id: 5 offset_ps: 1150000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 5000000000 duration_ps: 400000000 }
    events { metadata_id: 4 offset_ps: 5050000000 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 5100000000 duration_ps: 200000000 }
    events { metadata_id: 5 offset_ps: 5150000000 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 9900000000 duration_ps: 200000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.slice" } }
  event_metadata { key: 2 value { id: 2 name: "ingest.publish" } }
  event_metadata { key: 3 value { id: 3 name: "ingest.exchange" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(_lane_reduce)" } }
  event_metadata { key: 5 value { id: 5 name: "PjitFunction(_exchange)" } }
}
"""
FOUR_CHIPS = "".join(DEVICE.format(pid=i + 1, i=i, a=300 + 20 * i)
                     for i in range(4)) + HOST
PEAKS = json.loads((REPO / "bench" / "peaks.json").read_text())


def ctx(trace, *, k=2000, shards=4):
    def cost(layer):
        spec = importlib.util.spec_from_file_location(
            f"cost_{layer}", REPO / "bench" / "costs" / f"{layer}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    return SimpleNamespace(trace=trace, peaks=PEAKS["TPU v5 lite"],
                           cost=cost, cell=SimpleNamespace(
                               config={"k": k}, shards=shards))


@pytest.fixture(scope="module")
def four_chips():
    from jax.profiler import ProfileData
    return trace_reduce.reduce_profile(
        ProfileData.from_text_proto(FOUR_CHIPS))


def test_exchange_launches_tie_to_the_nested_span(four_chips):
    r = four_chips
    assert r.attribution == "name" and len(r.devices) == 4
    # (300 + 400, 320 + 400, 340 + 400, 360 + 400) µs over 4 devices
    assert r.launched_by(r"^ingest\.exchange$") == (2, 730_000)
    # the publish keeps only the lane reduce: the exchange's own span is
    # the innermost one open at its launch
    assert r.launched_by(r"^ingest\.publish$") == (2, 1_000_000)
    assert r.host_count(r"^ingest\.exchange$") == 2    # the third ends late


def test_exchange_device_ms_by_hand(four_chips):
    assert reader("exchange_device_ms")(ctx(four_chips)) == pytest.approx(
        730_000 / 2 / MS)
    # the publish reader now reads the local part alone
    assert reader("publish_device_ms")(ctx(four_chips)) == pytest.approx(
        1_000_000 / 2 / MS)


@pytest.mark.parametrize("k,shards", [(2000, 4), (8000, 4), (2000, 2)])
def test_exchange_roofline_by_hand(four_chips, k, shards):
    rounds = {4: 2, 2: 1}[shards]
    summary = 3 * k * 4                      # ids, counts, errors in int32
    ici_s = rounds * 2 * summary / (1600e9 / 8)
    hbm_s = rounds * 3 * summary / 819e9
    assert ici_s > hbm_s                     # ICI bounds it on a v5e
    want = 100 * ici_s * 2 / 730e-6          # two exchanges in 730 µs
    got = reader("exchange_roofline")(ctx(four_chips, k=k, shards=shards))
    assert got == pytest.approx(want)
    assert got < 1.0


CHIP_SLICE = Path(__file__).with_name("chip_slice_v5e.txtpb")


@pytest.mark.parametrize("name", ["exchange_device_ms", "exchange_roofline"])
def test_one_chip_has_nothing_to_read(name):
    """The slice recorded on one chip, and the four-chip trace with its
    exchange spans taken out (as the parent commit writes it)."""
    from jax.profiler import ProfileData
    one = trace_reduce.reduce_profile(
        ProfileData.from_text_proto(CHIP_SLICE.read_text()))
    assert reader(name)(ctx(one, shards=1)) is None
    parent = FOUR_CHIPS.replace('"ingest.exchange"', '"other"')
    no_span = trace_reduce.reduce_profile(
        ProfileData.from_text_proto(parent))
    assert reader(name)(ctx(no_span)) is None
