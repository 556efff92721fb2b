"""Unified merge core: every COMBINE/merge path under every kernel impl.

Covers the contract the engine relies on (DESIGN.md §6.3):
  * sorted / Pallas combine-match are bitwise-identical to the dense
    reference across random k, candidate widths, and fill levels;
  * COMBINE algebra (empty identity, bound preservation) holds under every
    impl;
  * the engine-resolved kernel reaches every reduction strategy (local tree
    and — via shard_map subprocesses — butterfly/allgather/hierarchical),
    with bitwise-equal results across impls;
  * butterfly_combine falls back to allgather on non-power-of-two axes.

``REPRO_TEST_KERNEL`` restricts the impl sweep (CI's kernel-matrix leg runs
one impl per job); unset, all four are exercised.  'fused' is the window-
level Pallas megakernel: at the sub-op surfaces (combine_match) it degrades
to 'sorted' by contract, and its real dispatch — ``ingest_window`` /
``combine_summaries`` — is covered by the bitwise state matrix at the
bottom of this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (EMPTY, Summary, combine, empty_like, init_summary,
                        merge_histogram, min_frequency, reduce_summaries,
                        update_chunk)
from repro.core.exact import exact_counts, overestimation_violations
from repro.engine import EngineConfig, SketchEngine
from repro.kernels import ops
from repro.kernels.ref import combine_match_ref

ALL_IMPLS = ("jnp", "sorted", "pallas", "fused", "sortjoin")
IMPLS = ((os.environ["REPRO_TEST_KERNEL"],)
         if os.environ.get("REPRO_TEST_KERNEL") else ALL_IMPLS)

DENSE = functools.partial(ops.combine_match, impl="jnp")


def _impl_fn(impl):
    return functools.partial(ops.combine_match, impl=impl)


def zipf(n, skew=1.2, seed=0, cap=10**6):
    r = np.random.default_rng(seed)
    return np.minimum(r.zipf(skew, n), cap).astype(np.int32)


def _summary_at_fill(k, fill, seed):
    """A summary with ~fill·k occupied counters (0.0 → empty, 1.0 → full)."""
    if fill == 0.0:
        return init_summary(k)
    n = max(int(2.5 * k * fill), 1)
    distinct_cap = max(int(k * fill), 1)
    stream = zipf(n, seed=seed) % distinct_cap          # bounds distinct ids
    return update_chunk(init_summary(k), jnp.asarray(stream))


def _assert_summaries_equal(a: Summary, b: Summary, msg=""):
    for name, x, y in zip(("items", "counts", "errors"), a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{msg} field={name}")


def _check_bounds(summary, stream_np):
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
# Bitwise equivalence of the combine-match impls across k and fill levels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("k", [16, 300, 1024])
@pytest.mark.parametrize("fill", [0.0, 0.4, 1.0])
def test_combine_impls_bitwise_equal_dense(impl, k, fill):
    s1 = _summary_at_fill(k, fill, seed=k)
    s2 = _summary_at_fill(k, 1.0 - fill / 2, seed=k + 1)
    ref = combine(s1, s2, match_fn=DENSE)
    out = combine(s1, s2, match_fn=_impl_fn(impl))
    _assert_summaries_equal(ref, out, msg=f"impl={impl} k={k} fill={fill}")


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("k,c", [(16, 64), (300, 128), (1024, 4096)])
def test_merge_histogram_impls_bitwise_equal(impl, k, c):
    s = _summary_at_fill(k, 0.7, seed=c)
    from repro.core import chunk_histogram
    h_items, h_weights = chunk_histogram(jnp.asarray(zipf(c, seed=c + 1)))
    ref = merge_histogram(s, h_items, h_weights, match_fn=DENSE)
    out = merge_histogram(s, h_items, h_weights, match_fn=_impl_fn(impl))
    _assert_summaries_equal(ref, out, msg=f"impl={impl} k={k} c={c}")


@pytest.mark.parametrize("impl", IMPLS)
def test_combine_match_raw_contract(impl):
    """The raw kernel outputs (incl. matched_s) agree with the dense ref."""
    rng = np.random.default_rng(7)
    k, c = 200, 96
    si = rng.choice(np.arange(-1, 4 * k), size=k, replace=False).astype(np.int32)
    ci = rng.choice(np.arange(-1, 4 * k), size=c, replace=False).astype(np.int32)
    cc = (rng.integers(1, 10**6, c) * (ci != -1)).astype(np.int32)
    ce = (rng.integers(0, 10**4, c) * (ci != -1)).astype(np.int32)
    args = tuple(map(jnp.asarray, (si, ci, cc, ce)))
    ref = combine_match_ref(*args)
    out = ops.combine_match(*args, impl=impl)
    for name, a, b in zip(("add_c", "add_e", "matched_s", "matched_c"),
                          ref, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"impl={impl} out={name}")
    # histogram mode: errors channel skipped, other outputs unchanged
    out_h = ops.combine_match(*args[:3], impl=impl)
    assert out_h[1] is None
    np.testing.assert_array_equal(np.asarray(out_h[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(out_h[2]), np.asarray(ref[2]))
    np.testing.assert_array_equal(np.asarray(out_h[3]), np.asarray(ref[3]))


# ---------------------------------------------------------------------------
# COMBINE algebra under every impl
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", IMPLS)
def test_empty_identity_under_impl(impl):
    fn = _impl_fn(impl)
    s = _summary_at_fill(128, 1.0, seed=3)
    for c in (combine(s, empty_like(s), match_fn=fn),
              combine(empty_like(s), s, match_fn=fn)):
        np.testing.assert_array_equal(
            np.sort(np.asarray(c.counts)), np.sort(np.asarray(s.counts)))
        np.testing.assert_array_equal(
            np.sort(np.asarray(c.items)), np.sort(np.asarray(s.items)))


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("fill", [0.3, 1.0])
def test_bound_preservation_under_impl(impl, fill):
    fn = _impl_fn(impl)
    k = 128
    st1 = zipf(int(4 * k * fill) + 64, skew=1.1, seed=5)
    st2 = zipf(6 * k, skew=1.3, seed=6)
    s1 = update_chunk(init_summary(k), jnp.asarray(st1))
    s2 = update_chunk(init_summary(k), jnp.asarray(st2))
    merged = combine(s1, s2, match_fn=fn)
    _check_bounds(merged, np.concatenate([st1, st2]))


# ---------------------------------------------------------------------------
# The engine-resolved kernel governs every merge (not just ingestion)
# ---------------------------------------------------------------------------

def test_engine_resolved_kernel_reaches_reduction(monkeypatch):
    seen = []
    real = ops.combine_match

    def spy(*args, **kwargs):
        seen.append(kwargs.get("impl", "auto"))
        return real(*args, **kwargs)

    monkeypatch.setattr(ops, "combine_match", spy)
    engine = SketchEngine(EngineConfig(k=64, tenants=4, chunk=32,
                                       buffer_depth=1, kernel="sorted",
                                       reduction="local"))
    st = engine.ingest(engine.init(),
                       jnp.asarray(zipf(4 * 64, seed=8).reshape(4, -1)))
    seen.clear()
    engine.merged(st)                       # traces flush-view + reduction
    assert seen and set(seen) == {"sorted"}, seen


@pytest.mark.parametrize("kernel", ["jnp", "sorted", "pallas", "fused",
                                    "sortjoin"])
def test_engine_merged_impls_agree(kernel):
    if kernel not in IMPLS and kernel != "jnp":
        pytest.skip(f"impl sweep restricted to {IMPLS}")
    stream = jnp.asarray(zipf(5 * 512, seed=9).reshape(5, -1))
    ref_engine = SketchEngine(EngineConfig(k=200, tenants=5, chunk=256,
                                           buffer_depth=2, kernel="jnp"))
    ref = ref_engine.merged(ref_engine.ingest(ref_engine.init(), stream))
    engine = SketchEngine(EngineConfig(k=200, tenants=5, chunk=256,
                                       buffer_depth=2, kernel=kernel))
    out = engine.merged(engine.ingest(engine.init(), stream))
    _assert_summaries_equal(ref, out, msg=f"kernel={kernel}")


def test_legacy_reduction_signature_still_works():
    from repro.engine import register_reduction
    from repro.engine import reductions as R

    def legacy(stacked, axis_names):          # no match_fn keyword
        return reduce_summaries(stacked)

    register_reduction("legacy_probe", legacy)
    try:
        engine = SketchEngine(EngineConfig(k=32, tenants=2, chunk=16,
                                           buffer_depth=1,
                                           reduction="legacy_probe"))
        st = engine.ingest(engine.init(),
                           jnp.asarray(zipf(2 * 16, seed=10).reshape(2, -1)))
        engine.merged(st)                     # must not raise
    finally:
        R._REGISTRY.pop("legacy_probe", None)


# ---------------------------------------------------------------------------
# Fused megakernel vs unfused window dispatch: bitwise across the state
# matrix (k × buffer fill × window shape) at BOTH window-level surfaces
# ---------------------------------------------------------------------------

def _batched_summary(k, fill, seed, b=2):
    rows = [_summary_at_fill(k, fill, seed=seed + i) for i in range(b)]
    return Summary(*(jnp.stack([getattr(r, f) for r in rows])
                     for f in ("items", "counts", "errors")))


def _window_block(k, w, pattern, seed, b=2):
    rng = np.random.default_rng(seed)
    if pattern == "dups":        # zipf: heavy duplication, like real traffic
        win = np.minimum(rng.zipf(1.2, size=(b, w)), 8 * k - 1)
    else:                        # all-distinct: every id absorbs separately
        win = np.stack([rng.choice(8 * k, size=w, replace=False)
                        for _ in range(b)])
    return jnp.asarray(win.astype(np.int32))


@pytest.mark.parametrize("k", [64, 2048])
@pytest.mark.parametrize("fill", [0.0, 0.4, 1.0])
@pytest.mark.parametrize("pattern", ["dups", "distinct"])
def test_fused_ingest_window_matrix_bitwise(k, fill, pattern):
    if "fused" not in IMPLS:
        pytest.skip(f"impl sweep restricted to {IMPLS}")
    s = _batched_summary(k, fill, seed=17 * k)
    window = _window_block(k, max(64, k // 4), pattern, seed=k + 3)
    fused = ops.ingest_window(s.items, s.counts, s.errors, window,
                              impl="fused")
    for ref_impl in ("sorted", "jnp"):
        ref = ops.ingest_window(s.items, s.counts, s.errors, window,
                                impl=ref_impl)
        _assert_summaries_equal(
            Summary(*fused), Summary(*ref),
            msg=f"fused-vs-{ref_impl} k={k} fill={fill} pattern={pattern}")


@pytest.mark.parametrize("k", [64, 2048])
@pytest.mark.parametrize("fill", [0.0, 0.4, 1.0])
def test_fused_combine_summaries_matrix_bitwise(k, fill):
    if "fused" not in IMPLS:
        pytest.skip(f"impl sweep restricted to {IMPLS}")
    s1 = _batched_summary(k, fill, seed=5 * k)
    s2 = _batched_summary(k, 1.0 - fill / 2, seed=5 * k + 2)
    fused = ops.combine_summaries(*s1, *s2, impl="fused")
    for ref_impl in ("sorted", "jnp"):
        ref = ops.combine_summaries(*s1, *s2, impl=ref_impl)
        _assert_summaries_equal(
            Summary(*fused), Summary(*ref),
            msg=f"fused-vs-{ref_impl} k={k} fill={fill}")


# ---------------------------------------------------------------------------
# Mesh reductions: kernel threading + butterfly non-power-of-two fallback
# (subprocesses so the XLA device-count override never leaks into pytest)
# ---------------------------------------------------------------------------

from conftest import run_distributed as _run  # noqa: E402


def test_mesh_reductions_route_kernel_and_agree():
    out = _run("""
import functools, jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core import init_summary, spacesaving_chunked
from repro.core.parallel import (allgather_combine, butterfly_combine,
                                 hierarchical_combine)
from repro.core.spacesaving import pvary_summary
from repro.kernels import ops
from repro.launch.mesh import make_mesh_shape

rng = np.random.default_rng(2)
stream = np.minimum(rng.zipf(1.2, 32_000), 10**6).astype(np.int32)
mesh = make_mesh_shape((2, 4), ("pod", "data"))
blocks = jnp.asarray(stream).reshape(8, -1)

def run(mode, impl):
    fn = functools.partial(ops.combine_match, impl=impl)
    def inner(block):
        s = pvary_summary(init_summary(128), ("pod", "data"))
        s = spacesaving_chunked(s, block[0], chunk_size=1000)
        if mode == "butterfly":
            s = butterfly_combine(butterfly_combine(s, "data", match_fn=fn),
                                  "pod", match_fn=fn)
        elif mode == "hier":
            s = hierarchical_combine(s, "data", "pod", match_fn=fn)
        else:
            s = allgather_combine(s, ("pod", "data"), match_fn=fn)
        return jax.tree.map(lambda x: x[None], s)
    out = shard_map(inner, mesh=mesh, in_specs=P(("pod", "data")),
                    out_specs=P(("pod", "data")))(blocks)
    return jax.tree.map(lambda a: a[0], out)

for mode in ("butterfly", "hier", "flat"):
    ref = run(mode, "jnp")
    got = run(mode, "sorted")
    for a, b in zip(ref, got):
        assert bool(jnp.array_equal(a, b)), mode
print("OK")
""", n_dev=8)
    assert "OK" in out


def test_butterfly_non_power_of_two_axis_falls_back():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core import init_summary, spacesaving_chunked
from repro.core.parallel import allgather_combine, butterfly_combine
from repro.core.spacesaving import pvary_summary
from repro.launch.mesh import make_mesh_shape

rng = np.random.default_rng(3)
stream = np.minimum(rng.zipf(1.2, 24_000), 10**6).astype(np.int32)
mesh = make_mesh_shape((6,), ("data",))       # 6 ranks: not a power of two
blocks = jnp.asarray(stream).reshape(6, -1)

def run(mode):
    def inner(block):
        s = pvary_summary(init_summary(96), ("data",))
        s = spacesaving_chunked(s, block[0], chunk_size=1000)
        s = (butterfly_combine(s, "data") if mode == "butterfly"
             else allgather_combine(s, ("data",)))
        return jax.tree.map(lambda x: x[None], s)
    out = shard_map(inner, mesh=mesh, in_specs=P("data"),
                    out_specs=P("data"))(blocks)
    return jax.tree.map(lambda a: a[0], out)

bf = run("butterfly")                          # must not crash on p=6
ag = run("allgather")
for a, b in zip(bf, ag):
    assert bool(jnp.array_equal(a, b))
print("OK")
""", n_dev=6)
    assert "OK" in out
