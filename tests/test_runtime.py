"""StreamRuntime: the sharded two-level ingestion runtime (DESIGN.md §8).

Single-device coverage (the multi-device sharded-vs-single-host matrix
runs in tests/test_sharding_dist.py subprocesses):

  * config/topology validation (RuntimeConfig, make_host_mesh, shards vs
    devices, hierarchical's missing cross-pod axis);
  * the single-shard runtime is bitwise-identical to a bare SketchEngine
    over the same block decomposition — including pending buffers;
  * the double-buffered feed path equals plain sequential ingestion;
  * snapshots carry per-worker provenance and monotonic versions;
  * the one-shot ``parallel_spacesaving`` equals the classical
    local-summaries + ParallelReduction composition bitwise.

``REPRO_TEST_KERNEL`` restricts the impl sweep (CI's kernel-matrix /
scaling-smoke legs pin one impl per job); unset, jnp + sorted run.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import local_summaries, reduce_summaries
from repro.core.parallel import block_decompose
from repro.data.synthetic import zipf_stream
from repro.engine import EngineConfig, SketchEngine
from repro.runtime import (DeviceFeed, RuntimeConfig, StreamRuntime,
                           host_block_iter, host_blocks,
                           parallel_spacesaving)

IMPLS = ((os.environ["REPRO_TEST_KERNEL"],)
         if os.environ.get("REPRO_TEST_KERNEL") else ("jnp", "sorted"))

K, LANES, CHUNK, DEPTH = 128, 4, 256, 4


def _runtime(lanes=LANES, **kw):
    eng = EngineConfig(k=K, tenants=lanes, chunk=CHUNK, buffer_depth=DEPTH,
                       kernel=kw.pop("kernel", "jnp"))
    return StreamRuntime(RuntimeConfig(engine=eng, **kw))


def _stream(n=20_000, seed=0):
    return jnp.asarray(zipf_stream(n, 1.2, seed=seed, max_id=10**5))


def _states_equal(a, b):
    for name, x, y in zip(("items", "counts", "errors"),
                          a.summary, b.summary):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"summary.{name}")
    np.testing.assert_array_equal(np.asarray(a.buffer), np.asarray(b.buffer))
    assert int(a.fill) == int(b.fill)
    np.testing.assert_array_equal(np.asarray(a.n), np.asarray(b.n))


# ---------------------------------------------------------------------------
# Config / topology validation
# ---------------------------------------------------------------------------

def test_runtime_config_validation():
    eng = EngineConfig(k=K, tenants=LANES)
    with pytest.raises(ValueError, match="shards"):
        RuntimeConfig(engine=eng, shards=0)
    with pytest.raises(ValueError, match="pods"):
        RuntimeConfig(engine=eng, pods=0)
    with pytest.raises(ValueError, match="divide"):
        RuntimeConfig(engine=eng, shards=4, pods=3)
    with pytest.raises(ValueError, match="feed_depth"):
        RuntimeConfig(engine=eng, feed_depth=0)
    with pytest.raises(ValueError, match="not registered"):
        RuntimeConfig(engine=eng, reduction="nope")


def test_make_host_mesh_errors_and_autosize():
    from repro.launch.mesh import make_host_mesh
    n = len(jax.devices())
    with pytest.raises(ValueError, match="available"):
        make_host_mesh(n_data=n + 1)
    mesh = make_host_mesh(n_data=None)          # auto-size to all devices
    assert mesh.devices.size == n


def test_runtime_shards_exceed_devices():
    with pytest.raises(ValueError, match="available"):
        _runtime(shards=len(jax.devices()) + 1)
    # the pods>1 topology raises the same friendly error, not jax's
    # generic mesh-shape failure
    with pytest.raises(ValueError, match="available"):
        _runtime(shards=2 * (len(jax.devices()) + 1), pods=2)


def test_hierarchical_missing_cross_pod_axis_is_clear():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core import hierarchical_combine, init_summary
    from repro.core.spacesaving import pvary_summary
    from repro.launch.mesh import make_mesh_shape

    mesh = make_mesh_shape((1,), ("data",))

    def run():
        def inner(_):
            s = pvary_summary(init_summary(16), ("data",))
            s = hierarchical_combine(s, "data", "pod")   # no "pod" axis
            return jax.tree.map(lambda a: a[None], s)
        return shard_map(inner, mesh=mesh, in_specs=P("data"),
                         out_specs=P("data"))(jnp.zeros((1,), jnp.int32))

    with pytest.raises(ValueError, match="cross-pod axis 'pod'"):
        run()


# ---------------------------------------------------------------------------
# Single-shard runtime == bare engine (bitwise, pending buffers included)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", IMPLS)
def test_single_shard_runtime_matches_engine(impl):
    rt = _runtime(shards=1, kernel=impl)
    eng = SketchEngine(EngineConfig(k=K, tenants=LANES, chunk=CHUNK,
                                    buffer_depth=DEPTH, reduction="local",
                                    kernel=impl))
    stream = _stream()
    st_rt = rt.ingest(rt.init(), stream)
    st_eng = eng.ingest(eng.init(), block_decompose(stream, LANES, CHUNK))
    _states_equal(st_rt, st_eng)

    snap_rt, snap_eng = rt.snapshot(st_rt), eng.snapshot(st_eng)
    for x, y in zip(snap_rt.summary, snap_eng.summary):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert int(snap_rt.n) == int(snap_eng.n)


@pytest.mark.parametrize("strategy", ["butterfly", "allgather",
                                      "hierarchical"])
def test_reduction_strategies_degrade_to_local_on_one_shard(strategy):
    stream = _stream()
    base = _runtime(shards=1, reduction="local")
    rt = _runtime(shards=1, reduction=strategy)
    m1 = base.merged(base.ingest(base.init(), stream))
    m2 = rt.merged(rt.ingest(rt.init(), stream))
    for x, y in zip(m1, m2):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Feed path (host blocks, double-buffered) == plain ingestion
# ---------------------------------------------------------------------------

def test_feed_matches_sequential_ingest():
    rt = _runtime(shards=1)
    blocks = [np.asarray(zipf_stream(rt.workers * CHUNK, 1.1, seed=i,
                                     max_id=10**5))
              for i in range(5)]
    fed = rt.feed(rt.init(), iter(blocks))
    seq = rt.init()
    for b in blocks:
        seq = rt.ingest(seq, jnp.asarray(b))
    _states_equal(fed, seq)


def test_feed_loop_donates_state_buffers_in_place():
    """The donated ingest twin aliases the pending buffer, not a copy.

    feed()'s loop threads state through ``_feed_ingest_fn`` (donated
    arg 0): the output state must reuse the donated input's buffer
    storage (no per-step round-trip copy of the (B, T, C) block), and
    the donated input must be invalidated afterwards.
    """
    rt = _runtime(shards=1)
    block = jnp.asarray(host_blocks(
        np.asarray(zipf_stream(rt.workers * CHUNK, 1.1, seed=1,
                               max_id=10**5)), rt.workers, CHUNK))
    # warm the donated program, then take a loop-internal state feed()
    # would own exclusively
    st = rt._feed_ingest_fn(rt.init(), block)
    ptr = st.buffer.unsafe_buffer_pointer()
    out = rt._feed_ingest_fn(st, block)
    assert out.buffer.unsafe_buffer_pointer() == ptr, \
        "donated buffer was copied instead of aliased in place"
    with pytest.raises(RuntimeError):
        np.asarray(st.buffer)              # donated input is dead


def test_feed_caller_state_survives_donation():
    """feed() never donates the CALLER's state argument (first step is
    the non-donating program), so it stays readable afterwards."""
    rt = _runtime(shards=1)
    st0 = rt.init()
    blocks = [np.asarray(zipf_stream(rt.workers * CHUNK, 1.1, seed=i,
                                     max_id=10**5)) for i in range(3)]
    fed = rt.feed(st0, iter(blocks))
    assert int(np.asarray(st0.fill)) == 0   # still alive and unchanged
    assert int(fed.n.sum()) == sum(len(b) for b in blocks)


def test_host_blocks_matches_block_decompose():
    stream = np.asarray(zipf_stream(10_000, 1.3, seed=3, max_id=10**4))
    hb = host_blocks(stream, 8, CHUNK)
    bd = np.asarray(block_decompose(jnp.asarray(stream), 8, CHUNK))
    np.testing.assert_array_equal(hb, bd)


def test_ingest_rejects_off_chunk_blocks():
    # a ragged pre-decomposed tail would be EMPTY-padded INSIDE the pending
    # buffer, silently shifting later chunk boundaries off the canonical
    # decomposition — rejected instead of truncated/misaligned.
    rt = _runtime(shards=1)
    with pytest.raises(ValueError, match="multiple of the engine chunk"):
        rt.ingest(rt.init(), jnp.ones((rt.workers, CHUNK + 1), jnp.int32))


def test_empty_stream_is_noop():
    rt = _runtime(shards=1)
    state0 = rt.init()
    # flat empty stream, empty pre-decomposed blocks, and an empty feed
    # block all leave the state untouched (no crash, no truncation)
    _states_equal(rt.ingest(state0, jnp.zeros((0,), jnp.int32)), state0)
    _states_equal(rt.ingest(state0, rt.decompose(jnp.zeros((0,), jnp.int32))),
                  state0)
    _states_equal(rt.feed(state0, [np.zeros((0,), np.int32)]), state0)
    assert rt.decompose(jnp.zeros((0,), jnp.int32)).shape \
        == (rt.workers, 0)
    snap = rt.snapshot(rt.feed(state0, iter([])))
    assert int(snap.n) == 0


def test_feed_final_partial_block_not_truncated():
    # last host block shorter than workers×chunk (a final partial chunk):
    # every item must land (EMPTY-padded, never dropped) and the result
    # must equal ingesting the same blocks one by one
    rt = _runtime(shards=1)
    sizes = [rt.workers * CHUNK, rt.workers * CHUNK // 2 + 7]
    blocks = [np.asarray(zipf_stream(s, 1.1, seed=i, max_id=10**5))
              for i, s in enumerate(sizes)]
    fed = rt.feed(rt.init(), iter(blocks))
    assert int(fed.n.sum()) == sum(sizes)
    seq = rt.init()
    for b in blocks:
        seq = rt.ingest(seq, jnp.asarray(host_blocks(b, rt.workers, CHUNK)))
    _states_equal(fed, seq)


def test_device_feed_preserves_order_and_depth():
    with pytest.raises(ValueError, match="depth"):
        DeviceFeed([], depth=0)
    blocks = [np.full((4,), i, np.int32) for i in range(7)]
    out = list(DeviceFeed(iter(blocks), depth=3))
    assert len(out) == 7
    for i, b in enumerate(out):
        np.testing.assert_array_equal(np.asarray(b), blocks[i])


def test_host_block_iter_chunking_invariant():
    # the emitted block sequence depends only on (workers, multiple,
    # block_items) — never on how the producer happened to slice the
    # stream into pieces
    stream = np.asarray(zipf_stream(10_000, 1.3, seed=5, max_id=10**4))
    bi = 4 * 32 * 2                 # two (workers × multiple) layers
    ref = [host_blocks(stream[i:i + bi], 4, 32)
           for i in range(0, stream.size, bi)]
    for n_pieces in (1, 7, 23):
        got = list(host_block_iter(np.array_split(stream, n_pieces),
                                   4, 32, block_items=bi))
        assert len(got) == len(ref)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(a, b)


def test_host_block_iter_pads_trailing_remainder():
    # 10 items into a (4, 8) layer: same EMPTY padding host_blocks applies
    stream = np.arange(10, dtype=np.int32)
    (block,) = host_block_iter([stream], 4, 8, block_items=32)
    np.testing.assert_array_equal(block, host_blocks(stream, 4, 8))


def test_host_block_iter_is_lazy():
    # an unbounded chunk generator must stream with O(block) memory —
    # blocks come out while the input is still being produced
    def endless():
        i = 0
        while True:
            yield np.arange(i, i + 100, dtype=np.int32)
            i += 100
    it = host_block_iter(endless(), 2, 16, block_items=64)
    first, second = next(it), next(it)
    assert first.shape == second.shape == (2, 32)
    np.testing.assert_array_equal(first.reshape(-1), np.arange(64))
    np.testing.assert_array_equal(second.reshape(-1), np.arange(64, 128))


def test_host_block_iter_drives_ingest_like_feed():
    # streaming decomposition + DeviceFeed == rt.feed over the same block
    # boundaries: the generator path changes memory footprint, not results
    rt = _runtime(shards=1)
    bi = rt.workers * CHUNK
    stream = np.asarray(zipf_stream(3 * bi + 57, 1.1, seed=9, max_id=10**5))
    ref = rt.feed(rt.init(),
                  [stream[i:i + bi] for i in range(0, stream.size, bi)])
    staged = DeviceFeed(
        host_block_iter(np.array_split(stream, 11), rt.workers, CHUNK,
                        block_items=bi),
        sharding=rt.block_sharding())
    state = rt.init()
    for block in staged:
        state = rt.ingest(state, block)
    _states_equal(state, ref)


# ---------------------------------------------------------------------------
# Snapshot provenance
# ---------------------------------------------------------------------------

def test_snapshot_provenance_and_versions():
    rt = _runtime(shards=1, kernel="sorted")
    # 19k items → 19 chunks per lane → fill = 19 % DEPTH = 3 pending chunks
    st = rt.ingest(rt.init(), _stream(19_000))
    s1 = rt.snapshot(st)
    s2 = rt.snapshot(st)
    assert (s1.version, s2.version) == (1, 2)
    assert s1.tenants == rt.workers
    assert s1.shard_n.shape == (rt.workers,)
    assert int(s1.shard_n.sum()) == int(s1.n)
    assert s1.kernel == "sorted"
    # reads never flush: the pending buffer is untouched by snapshotting
    assert int(st.fill) > 0


# ---------------------------------------------------------------------------
# One-shot API (Algorithm 1)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("p", [1, 4, 8])
def test_oneshot_matches_classical_composition(p, impl):
    stream = _stream(40_000, seed=7)
    got = parallel_spacesaving(stream, k=K, p=p, chunk_size=CHUNK,
                               kernel=impl)
    want = reduce_summaries(
        local_summaries(stream, p=p, k=K, chunk_size=CHUNK))
    for name, x, y in zip(("items", "counts", "errors"), got, want):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=name)


def test_core_reexports_are_runtime_backed():
    from repro.core import parallel_spacesaving as core_pss
    stream = _stream(8_000, seed=9)
    a = core_pss(stream, k=64, p=2, chunk_size=CHUNK)
    b = parallel_spacesaving(stream, k=64, p=2, chunk_size=CHUNK)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
