"""Compile rehearsal for the chip: the main path's Pallas kernels and one
engine ingest step, compiled for a described TPU v5e with no chip attached.

Interpret mode cannot show what Mosaic refuses (block tiling, VMEM); these
compiles can. The topology is described inside a fixture, never at import:
only one process may hold the TPU compiler library, and every test worker
imports this file. The persistent compilation cache is off around these
compiles — what they write could not be read back without a chip.
"""
import functools

import pytest

PAPER_K, WINDOW, QUERIES = 2000, 16384, 512   # Table I k; T·C; read batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the kernel wrappers to lower for Mosaic, not to interpret."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _shapes(sharding, *sizes):
    import jax
    import jax.numpy as jnp
    return [jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding)
            for n in sizes]


def _kernel_cases():
    from repro.kernels import ops
    return {
        "combine_match": (ops.combine_match,
                          (PAPER_K, WINDOW, WINDOW, WINDOW)),
        "match_weights": (ops.match_weights, (PAPER_K, WINDOW, WINDOW)),
        "query": (ops.query, (PAPER_K, PAPER_K, PAPER_K, QUERIES)),
    }


@pytest.mark.parametrize("op", ["combine_match", "match_weights", "query"])
def test_kernel_compiles_at_paper_k(op, one_chip, on_tpu):
    import jax
    fn, sizes = _kernel_cases()[op]
    compiled = jax.jit(functools.partial(fn, impl="pallas")).lower(
        *_shapes(one_chip, *sizes)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_engine_ingest_step_compiles_at_paper_default(one_chip, on_tpu):
    """One SketchEngine._ingest step at PAPER_STREAM_CONFIGS
    ["paper-default"] with the serving geometry (8 lanes, chunk 2048,
    depth 8) and the Pallas matcher the static plan picks on a TPU."""
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import PAPER_STREAM_CONFIGS
    from repro.engine import EngineConfig, SketchEngine

    k = PAPER_STREAM_CONFIGS["paper-default"]["k_counters"]
    eng = SketchEngine(EngineConfig(k=k, tenants=8, chunk=2048,
                                    buffer_depth=8, kernel="pallas"))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng.state_shapes())
    block = jax.ShapeDtypeStruct((8, 2048), jnp.int32, sharding=one_chip)
    compiled = jax.jit(eng._ingest).lower(state, block).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _tpu_plan(k):
    """The static plan a TPU resolves at counter budget k."""
    from repro.plan import PLAN_OPS, ExecutionPlan, static_impl
    return ExecutionPlan(
        fingerprint="v5e-rehearsal", source="static",
        kernels={op: {k: static_impl(op, k, on_tpu=True)} for op in PLAN_OPS},
        reductions={}, pods={})


def _static_tpu_ingest(k, width, one_chip):
    """(flush impl, lowered program) of SketchEngine._ingest at k (8 lanes,
    chunk 2048, depth 8) on the static TPU plan, for a group of ``width``
    blocks of 16,384 ids: an (8, width·2048) block."""
    import jax
    import jax.numpy as jnp

    from repro.engine import EngineConfig, SketchEngine
    from repro.plan import use_plan

    with use_plan(_tpu_plan(k)):
        eng = SketchEngine(EngineConfig(k=k, tenants=8, chunk=2048,
                                        buffer_depth=8))
        flush = eng.config.resolved_flush_kernel()
        state = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            eng.state_shapes())
        block = jax.ShapeDtypeStruct((8, width * 2048), jnp.int32,
                                     sharding=one_chip)
        lowered = jax.jit(eng._ingest).lower(state, block)
    return flush, lowered


def _compile_side_by_side(lowered):
    """{key: compiled text} of a dict of lowered programs, one thread a
    program (XLA compiles outside the interpreter lock): one after
    another, the coalesced widths take about 25 s each."""
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(len(lowered)) as pool:
        texts = pool.map(lambda lo: lo.compile().as_text(), lowered.values())
        return dict(zip(lowered, texts))


@pytest.mark.parametrize("k", [PAPER_K, 8000])
def test_engine_ingest_step_compiles_with_static_tpu_flush(k, one_chip,
                                                           on_tpu):
    """One SketchEngine._ingest step at k = 2000 and 8000 (8 lanes, chunk
    2048, depth 8) with the flush impl the static TPU plan resolves. The
    two-sort join builds nothing of k × W elements and no Pallas call."""
    import math
    import re

    from repro.plan import static_impl

    flush, lowered = _static_tpu_ingest(k, 1, one_chip)
    text = lowered.compile().as_text()
    assert flush == static_impl("flush", k, on_tpu=True)
    if flush == "pallas":
        assert "tpu_custom_call" in text
        return
    assert flush == "sortjoin"
    assert "tpu_custom_call" not in text
    largest = max(math.prod(int(d) for d in dims.split(",") if d)
                  for dims in re.findall(r"\b[a-z]+\d*\[([\d,]*)\]", text))
    assert 8 * (k + WINDOW) <= largest < k * WINDOW, largest


COALESCED_KS, COALESCED_WIDTHS = (PAPER_K, 8000), (2, 4, 8)


@pytest.fixture(scope="module")
def coalesced_one_chip(one_chip):
    """{(k, width): (flush impl, compiled text)} of the one-chip ingest
    program for every coalesced group the tests below check."""
    from repro.kernels import ops

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_on_tpu", lambda: True)
        cases = {(k, w): _static_tpu_ingest(k, w, one_chip)
                 for k in COALESCED_KS for w in COALESCED_WIDTHS}
    texts = _compile_side_by_side({c: lo for c, (_, lo) in cases.items()})
    return {c: (flush, texts[c]) for c, (flush, _) in cases.items()}


@pytest.mark.parametrize("width", COALESCED_WIDTHS)
@pytest.mark.parametrize("k", COALESCED_KS)
def test_coalesced_ingest_compiles_on_one_chip(k, width, coalesced_one_chip):
    """The serving loop's coalesced groups (serve/ingest.py group_widths:
    2, 4 and 8 blocks at the paper's publish_every 8) compile as one
    ingest program each: an (8, width·2048) block scanned in order."""
    flush, text = coalesced_one_chip[k, width]
    assert flush == "sortjoin"
    assert "tpu_custom_call" not in text


@pytest.fixture(scope="module")
def four_chips(one_chip, topo):
    """The described v5e:2x2's 4 devices (after ``one_chip``, which turns
    the persistent compilation cache off)."""
    return topo.devices[:4]


def _four_chip_ingest(devices, monkeypatch, k, width):
    """A StreamRuntime of 4 shards × 8 lanes (chunk 2048, depth 8) on the
    described devices, with the shapes of its state and of a group of
    ``width`` blocks of 65,536 ids: a (32, width·2048) block. The runtime
    builds its mesh from ``jax.devices()``, steered here to the devices;
    the caller holds the plan."""
    import jax
    import jax.numpy as jnp

    from repro.engine import EngineConfig
    from repro.runtime import RuntimeConfig, StreamRuntime

    devices = list(devices)
    make_mesh = jax.make_mesh
    monkeypatch.setattr(jax, "devices", lambda *a, **kw: devices)
    monkeypatch.setattr(jax, "make_mesh", lambda shape, axes, **kw:
                        make_mesh(shape, axes, **{**kw, "devices": devices}))
    rt = StreamRuntime(RuntimeConfig(
        engine=EngineConfig(k=k, tenants=8, chunk=2048, buffer_depth=8),
        shards=4, reduction="auto"))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        jax.eval_shape(rt.init), rt.state_shardings())
    block = jax.ShapeDtypeStruct((32, width * 2048), jnp.int32,
                                 sharding=rt.block_sharding())
    return rt, state, block


def test_sharded_publish_and_ingest_compile_for_four_chips(four_chips,
                                                           on_tpu,
                                                           monkeypatch):
    """The paper's hybrid deployment at k = 2000: 4 shards × 8 lanes, chunk
    2048, depth 8, with the static TPU plan. The ingest and both publish
    programs compile for the 4 devices; the COMBINEs of the publish are
    Pallas calls; a collective-permute (the butterfly's ``ppermute``) is
    in the exchange program and in no other. The runtime builds its mesh
    from ``jax.devices()``, steered here to the described devices."""
    import jax
    import jax.numpy as jnp

    from repro.core.spacesaving import Summary
    from repro.plan import use_plan

    with use_plan(_tpu_plan(PAPER_K)):
        rt, state, block = _four_chip_ingest(four_chips, monkeypatch,
                                             PAPER_K, 1)
        assert rt.engine.config.reduction == "butterfly"
        row = jax.ShapeDtypeStruct((4, PAPER_K), jnp.int32,
                                   sharding=rt.block_sharding())
        texts = {
            "ingest": rt._ingest_blocks_fn.lower(state, block),
            "lane_reduce": rt._lane_reduce_fn.lower(state),
            "exchange": rt._exchange_fn.lower(Summary(row, row, row)),
        }
        texts = {k: v.compile().as_text() for k, v in texts.items()}
    for name, text in texts.items():
        assert "num_partitions=4" in text, name
        assert ("collective-permute" in text) == (name == "exchange"), name
    assert "tpu_custom_call" in texts["lane_reduce"]
    assert "tpu_custom_call" in texts["exchange"]


@pytest.fixture(scope="module")
def coalesced_four_chips(four_chips):
    """{(k, width): compiled text} of the 4-chip runtime's ingest program
    for every coalesced group the test below checks."""
    from repro.kernels import ops
    from repro.plan import use_plan

    lowered = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_on_tpu", lambda: True)
        for k in COALESCED_KS:
            with use_plan(_tpu_plan(k)):
                for w in COALESCED_WIDTHS:
                    rt, state, block = _four_chip_ingest(four_chips, mp, k, w)
                    lowered[k, w] = rt._ingest_blocks_fn.lower(state, block)
    return _compile_side_by_side(lowered)


@pytest.mark.parametrize("width", COALESCED_WIDTHS)
@pytest.mark.parametrize("k", COALESCED_KS)
def test_coalesced_ingest_compiles_for_four_chips(k, width,
                                                  coalesced_four_chips):
    """The serving loop's coalesced groups of 2, 4 and 8 blocks on the
    4-chip mesh: the runtime's ingest program for a (32, width·2048) block
    compiles for the 4 devices, with no collective in it."""
    text = coalesced_four_chips[k, width]
    assert "num_partitions=4" in text
    assert "collective-permute" not in text
    assert "all-reduce" not in text
