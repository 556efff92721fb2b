"""SketchEngine: buffered-vs-unbuffered equivalence, COMBINE algebra on
batched states, invariant preservation after deferred merges, kernel
dispatch and the reduction registry."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (EMPTY, combine, estimate, init_summary,
                        min_frequency, pad_stream, reduce_summaries,
                        update_chunk)
from repro.core.exact import exact_counts, overestimation_violations
from repro.engine import (EngineConfig, SketchEngine, get_reduction,
                          reduction_names, register_reduction)
from repro.kernels import ops
from repro.kernels.ref import (match_weights_ref, match_weights_sorted,
                               query_ref, query_sorted)


def zipf(n, skew=1.2, seed=0, cap=10**6):
    r = np.random.default_rng(seed)
    return np.minimum(r.zipf(skew, n), cap).astype(np.int32)


def _tree_equal(a, b):
    return all(bool(jnp.array_equal(x, y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _check_invariants(summary, stream_np):
    assert overestimation_violations(summary, stream_np) == 0
    items = np.asarray(summary.items)
    errors = np.asarray(summary.errors)
    m = int(min_frequency(summary))
    if (items != EMPTY).all():
        assert (errors <= m).all()
    n, k = len(stream_np), summary.items.shape[-1]
    monitored = set(items[items != EMPTY].tolist())
    for x, f in exact_counts(stream_np).items():
        if f > n / k:
            assert x in monitored, (x, f, n, k)


# ---------------------------------------------------------------------------
# Buffered-vs-unbuffered equivalence (the flush exactness contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth", [1, 2, 8])
@pytest.mark.parametrize("n_chunks", [3, 8, 13])   # partial + full windows
def test_deferred_flush_matches_update_chunk_on_windows(depth, n_chunks):
    """flush() in 'deferred' mode is bitwise update_chunk over each
    T-chunk window — one top_k per T chunks instead of per chunk."""
    k, c = 64, 32
    stream = zipf(n_chunks * c, seed=1)
    engine = SketchEngine(EngineConfig(k=k, tenants=1, chunk=c,
                                       buffer_depth=depth,
                                       flush_mode="deferred"))
    st = engine.init()
    manual = init_summary(k)
    for w0 in range(0, n_chunks, depth):
        window = stream[w0 * c:(w0 + depth) * c]
        for i in range(w0, min(w0 + depth, n_chunks)):
            st = engine.update(st, jnp.asarray(stream[i * c:(i + 1) * c]))
        manual = update_chunk(
            manual, pad_stream(jnp.asarray(window), depth * c))
    st = engine.flush(st)
    assert _tree_equal(jax.tree.map(lambda a: a[0], st.summary), manual)
    assert int(st.fill) == 0
    assert int(st.n[0]) == stream.size


@pytest.mark.parametrize("depth", [1, 3, 8])
def test_replay_flush_matches_per_chunk_fold(depth):
    """flush() in 'replay' mode is bitwise the per-chunk update_chunk fold,
    at any buffer depth and fill level."""
    k, c, n_chunks = 48, 24, 7
    stream = zipf(n_chunks * c, seed=2)
    engine = SketchEngine(EngineConfig(k=k, tenants=1, chunk=c,
                                       buffer_depth=depth,
                                       flush_mode="replay"))
    st = engine.init()
    manual = init_summary(k)
    for i in range(n_chunks):
        ch = jnp.asarray(stream[i * c:(i + 1) * c])
        st = engine.update(st, ch)
        manual = update_chunk(manual, ch)
    st = engine.flush(st)
    assert _tree_equal(jax.tree.map(lambda a: a[0], st.summary), manual)


def test_ingest_equals_manual_updates_multi_tenant():
    b, k, c, depth = 4, 32, 16, 4
    stream = zipf(b * 11 * c, seed=3).reshape(b, -1)
    engine = SketchEngine(EngineConfig(k=k, tenants=b, chunk=c,
                                       buffer_depth=depth))
    st_a = engine.ingest(engine.init(), jnp.asarray(stream))
    st_b = engine.init()
    for i in range(stream.shape[1] // c):
        st_b = engine.update(st_b, jnp.asarray(stream[:, i * c:(i + 1) * c]))
    assert _tree_equal(st_a, st_b)
    assert int(st_a.fill) == (stream.shape[1] // c) % depth


def test_update_auto_flushes_at_depth():
    engine = SketchEngine(EngineConfig(k=16, tenants=1, chunk=8,
                                       buffer_depth=3))
    st = engine.init()
    for i in range(3):
        assert int(st.fill) == i
        st = engine.update(st, jnp.arange(8, dtype=jnp.int32) + i)
    assert int(st.fill) == 0                       # auto-flush fired
    assert int(st.summary.counts.sum()) > 0
    assert bool((st.buffer == EMPTY).all())


def test_update_pads_short_chunks():
    engine = SketchEngine(EngineConfig(k=16, tenants=1, chunk=32,
                                       buffer_depth=2))
    st = engine.update(engine.init(), jnp.asarray([5, 5, 7], jnp.int32))
    assert int(st.n[0]) == 3
    f, lo, mon = engine.estimate(st, jnp.asarray([5, 7, 9], jnp.int32))
    assert f.tolist() == [2, 1, 0]


def test_merged_is_pure_and_includes_pending():
    engine = SketchEngine(EngineConfig(k=32, tenants=2, chunk=16,
                                       buffer_depth=8))
    st = engine.update(engine.init(),
                       jnp.full((2, 16), 3, jnp.int32))    # pending only
    merged = engine.merged(st)
    assert int(merged.counts.sum()) == 32          # pending chunks visible
    assert int(st.fill) == 1                       # ...but still pending


# ---------------------------------------------------------------------------
# COMBINE algebra on batched states
# ---------------------------------------------------------------------------

def _batched_summaries(seeds, k=48, per=3_000):
    streams = [zipf(per, seed=s) for s in seeds]
    summaries = [update_chunk(init_summary(k), jnp.asarray(s))
                 for s in streams]
    stack = jax.tree.map(lambda *xs: jnp.stack(xs), *summaries)
    return stack, streams


def test_combine_commutative_on_batched_states():
    """COMBINE(a, b) ~ COMBINE(b, a): identical count multisets and both
    valid for the union stream (slot order/tie-breaks may differ)."""
    s1, st1 = _batched_summaries([1, 2, 3])
    s2, st2 = _batched_summaries([4, 5, 6])
    ab = jax.vmap(combine)(s1, s2)
    ba = jax.vmap(combine)(s2, s1)
    for i in range(3):
        ci = np.sort(np.asarray(ab.counts[i]))
        cj = np.sort(np.asarray(ba.counts[i]))
        np.testing.assert_array_equal(ci, cj)
        union = np.concatenate([st1[i], st2[i]])
        _check_invariants(jax.tree.map(lambda a: a[i], ab), union)
        _check_invariants(jax.tree.map(lambda a: a[i], ba), union)


def test_combine_associative_on_batched_states():
    s1, st1 = _batched_summaries([7, 8])
    s2, st2 = _batched_summaries([9, 10])
    s3, st3 = _batched_summaries([11, 12])
    left = jax.vmap(combine)(jax.vmap(combine)(s1, s2), s3)
    right = jax.vmap(combine)(s1, jax.vmap(combine)(s2, s3))
    for i in range(2):
        union = np.concatenate([st1[i], st2[i], st3[i]])
        _check_invariants(jax.tree.map(lambda a: a[i], left), union)
        _check_invariants(jax.tree.map(lambda a: a[i], right), union)
        # both orders report every true heavy hitter with valid bounds
        n, k = union.size, left.items.shape[-1]
        heavy = {x for x, f in exact_counts(union).items() if f > n / k}
        for s in (left, right):
            items = np.asarray(s.items[i])
            assert heavy.issubset(set(items[items != EMPTY].tolist()))


# ---------------------------------------------------------------------------
# Invariant preservation after deferred merges
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("flush_mode", ["deferred", "replay"])
@pytest.mark.parametrize("depth", [2, 8])
def test_invariants_after_buffered_merges(flush_mode, depth):
    b, k, c = 3, 64, 128
    stream = zipf(b * 10 * c, skew=1.1, seed=21).reshape(b, -1)
    engine = SketchEngine(EngineConfig(k=k, tenants=b, chunk=c,
                                       buffer_depth=depth,
                                       flush_mode=flush_mode))
    st = engine.ingest(engine.init(), jnp.asarray(stream))
    # per-tenant invariants vs each tenant's stream
    flushed = engine.flush(st)
    for i in range(b):
        _check_invariants(jax.tree.map(lambda a: a[i], flushed.summary),
                          stream[i])
    # merged invariants vs the union stream
    _check_invariants(engine.merged(st), stream.reshape(-1))


def test_estimate_matches_core_estimate():
    engine = SketchEngine(EngineConfig(k=64, tenants=2, chunk=64,
                                       buffer_depth=4))
    st = engine.ingest(engine.init(),
                       jnp.asarray(zipf(2 * 512, seed=31).reshape(2, -1)))
    queries = jnp.asarray([1, 2, 3, 17, 999_999], jnp.int32)
    f_e, lo_e, mon_e = engine.estimate(st, queries)
    f_c, lo_c, mon_c = estimate(engine.merged(st), queries)
    np.testing.assert_array_equal(np.asarray(f_e), np.asarray(f_c))
    np.testing.assert_array_equal(np.asarray(lo_e), np.asarray(lo_c))
    np.testing.assert_array_equal(np.asarray(mon_e), np.asarray(mon_c))


def test_absorb_histogram_exact_counts():
    engine = SketchEngine(EngineConfig(k=32, tenants=1, chunk=32,
                                       buffer_depth=1))
    counts = jnp.asarray([0, 5, 3, 0, 9], jnp.int32)
    st = engine.absorb_histogram(
        engine.init(), jnp.arange(5, dtype=jnp.int32), counts)
    assert int(st.summary.counts.sum()) == 17
    assert int(st.n[0]) == 17
    f, lo, mon = engine.estimate(st, jnp.asarray([1, 2, 4], jnp.int32))
    assert f.tolist() == [5, 3, 9]
    assert lo.tolist() == [5, 3, 9]               # exact: zero error


# ---------------------------------------------------------------------------
# Kernel dispatch + reduction registry
# ---------------------------------------------------------------------------

def _distinct_inputs(rng, k, c):
    s_items = rng.choice(np.arange(-1, 8 * k), size=k,
                         replace=False).astype(np.int32)
    h_items = rng.choice(np.arange(-1, 8 * k), size=c,
                         replace=False).astype(np.int32)
    h_weights = (rng.integers(1, 100, c) * (h_items != -1)).astype(np.int32)
    return tuple(map(jnp.asarray, (s_items, h_items, h_weights)))


@pytest.mark.parametrize("k,c", [(16, 8), (300, 100), (1024, 512)])
def test_sorted_match_bitwise_equals_ref(rng, k, c):
    si, hi, hw = _distinct_inputs(rng, k, c)
    for fn in (match_weights_sorted,
               lambda *a: ops.match_weights(*a, impl="sorted")):
        aw, m = fn(si, hi, hw)
        aw_r, m_r = match_weights_ref(si, hi, hw)
        np.testing.assert_array_equal(np.asarray(aw), np.asarray(aw_r))
        np.testing.assert_array_equal(np.asarray(m), np.asarray(m_r))


def test_sorted_query_bitwise_equals_ref(rng):
    k, q = 200, 64
    si = rng.choice(np.arange(-1, 4 * k), size=k, replace=False).astype(np.int32)
    sc = (rng.integers(0, 1000, k) * (si != -1)).astype(np.int32)
    se = (rng.integers(0, 50, k) * (si != -1)).astype(np.int32)
    qs = rng.integers(-1, 8 * k, q).astype(np.int32)
    args = tuple(map(jnp.asarray, (si, sc, se, qs)))
    for out in (query_sorted(*args), ops.query(*args, impl="sorted")):
        ref = query_ref(*args)
        for a, b in zip(out, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_kernel_impls_agree():
    stream = jnp.asarray(zipf(4 * 256, seed=41).reshape(1, -1))
    results = []
    for kernel in ("jnp", "sorted"):
        engine = SketchEngine(EngineConfig(k=300, tenants=1, chunk=256,
                                           buffer_depth=2, kernel=kernel))
        results.append(engine.flush(engine.ingest(engine.init(), stream)))
    assert _tree_equal(results[0], results[1])


def test_config_validation():
    with pytest.raises(ValueError):
        EngineConfig(k=0)
    with pytest.raises(ValueError):
        EngineConfig(flush_mode="later")
    with pytest.raises(ValueError):
        EngineConfig(kernel="cuda")
    with pytest.raises(ValueError):
        EngineConfig(reduction="ring")
    with pytest.raises(ValueError):
        EngineConfig(buffer_depth=0)


def test_reduction_registry():
    assert {"local", "butterfly", "allgather",
            "hierarchical"} <= set(reduction_names())
    with pytest.raises(KeyError):
        get_reduction("nope")
    with pytest.raises(ValueError):
        register_reduction("local", lambda s, a: s)   # no silent overwrite

    calls = []

    def probe(stacked, axis_names):
        calls.append(axis_names)
        return reduce_summaries(stacked)

    register_reduction("probe", probe)
    try:
        engine = SketchEngine(EngineConfig(k=16, tenants=2, chunk=8,
                                           buffer_depth=1,
                                           reduction="probe",
                                           axis_names=("data",)))
        st = engine.ingest(engine.init(),
                           jnp.asarray(zipf(2 * 8, seed=51).reshape(2, -1)))
        engine.merged(st)
        assert calls and calls[0] == ("data",)
    finally:
        from repro.engine import reductions as R
        R._REGISTRY.pop("probe", None)


def test_local_reduction_equals_reduce_summaries():
    b = 3
    stream = zipf(b * 1024, seed=61).reshape(b, -1)
    engine = SketchEngine(EngineConfig(k=64, tenants=b, chunk=256,
                                       buffer_depth=2, reduction="local"))
    st = engine.flush(engine.ingest(engine.init(), jnp.asarray(stream)))
    direct = reduce_summaries(st.summary)
    assert _tree_equal(engine.merged(st), direct)


# ---------------------------------------------------------------------------
# Name scopes: the flush stages, the publish merge and the reads carry
# their names into the programs' metadata (what a profile's per-op events
# show)
# ---------------------------------------------------------------------------

FLUSH_SCOPES = ("sketch.flush", "sketch.histogram", "sketch.match",
                "sketch.absorb", "sketch.topk")


def _scoped_program(kernel, program):
    """(lowered program, the scope names it must carry)."""
    from repro.service.frontend import _estimate_fn, _sorted_desc
    eng = SketchEngine(EngineConfig(k=16, tenants=2, chunk=32,
                                    buffer_depth=2, kernel=kernel))
    st = eng.init()
    s = jax.tree.map(lambda a: a[0], st.summary)
    if program == "ingest":
        stream = jnp.asarray(zipf(2 * 64).reshape(2, 64))
        return eng.ingest.lower(st, stream), FLUSH_SCOPES
    if program == "merged":
        return eng.merged.lower(st), FLUSH_SCOPES + ("sketch.merge",
                                                     "sketch.lane_reduce")
    if program == "estimate":
        return (_estimate_fn(kernel).lower(s.items, s.counts, s.errors,
                                           jnp.zeros((8,), jnp.int32)),
                ("sketch.query.estimate",))
    return _sorted_desc.lower(s), ("sketch.query.top",)


@pytest.mark.parametrize("kernel", ["jnp", "pallas"])
@pytest.mark.parametrize("program", ["ingest", "merged", "estimate", "top"])
def test_lowered_programs_carry_the_scope_names(kernel, program):
    lowered, scopes = _scoped_program(kernel, program)
    text = lowered.as_text(debug_info=True)
    for scope in scopes:
        # a component of an op's name stack, e.g. ".../sketch.flush/..."
        # or "vmap(sketch.histogram)/..."
        assert re.search(rf'[/("]{re.escape(scope)}[/)]', text), (
            program, scope)
