"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import (combine_match_ref, combine_match_sorted,
                               combine_match_sortjoin, match_weights_ref,
                               query_ref)

SHAPES = [(8, 16), (100, 57), (512, 512), (1000, 300), (64, 2048), (2048, 64)]


def _mk_inputs(rng, k, c, id_range=60):
    s_items = rng.integers(-1, id_range, k).astype(np.int32)
    hist = np.unique(rng.integers(0, id_range, c).astype(np.int32))
    h_items = np.full(c, -1, np.int32)
    h_items[:len(hist)] = hist
    h_weights = (rng.integers(1, 100, c) * (h_items != -1)).astype(np.int32)
    return jnp.asarray(s_items), jnp.asarray(h_items), jnp.asarray(h_weights)


@pytest.mark.parametrize("k,c", SHAPES)
def test_match_weights_pallas_vs_ref(rng, k, c):
    si, hi, hw = _mk_inputs(rng, k, c)
    aw_p, m_p = ops.match_weights(si, hi, hw, impl="pallas")
    aw_r, m_r = match_weights_ref(si, hi, hw)
    np.testing.assert_array_equal(np.asarray(aw_p), np.asarray(aw_r))
    np.testing.assert_array_equal(np.asarray(m_p), np.asarray(m_r))


@pytest.mark.parametrize("block", [8, 64, 256])
def test_match_weights_block_sweep(rng, block):
    si, hi, hw = _mk_inputs(rng, 200, 130)
    aw_p, m_p = ops.match_weights(si, hi, hw, impl="pallas",
                                  block_k=block, block_c=max(block, 128))
    aw_r, m_r = match_weights_ref(si, hi, hw)
    np.testing.assert_array_equal(np.asarray(aw_p), np.asarray(aw_r))
    np.testing.assert_array_equal(np.asarray(m_p), np.asarray(m_r))


def test_match_weights_sorted_empty_slots(rng):
    """EMPTY may repeat in s_items; the sorted impl must never match it."""
    si = jnp.asarray([-1, -1, 3, -1, 9], jnp.int32)
    hi = jnp.asarray([-1, 3, 7, 9, -1], jnp.int32)
    hw = jnp.asarray([0, 5, 2, 4, 0], jnp.int32)
    aw, m = ops.match_weights(si, hi, hw, impl="sorted")
    np.testing.assert_array_equal(np.asarray(aw), [0, 0, 5, 0, 4])
    np.testing.assert_array_equal(np.asarray(m),
                                  [False, True, False, True, False])


def test_pallas_interprets_on_cpu_only(monkeypatch):
    """Compiled on TPU, interpreted on CPU, refused on any other backend."""
    assert ops._interpret() is True          # the tests' CPU backend
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert ops._interpret() is False
    monkeypatch.setattr(ops, "_on_tpu", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu' is neither"):
        ops._interpret()


def test_match_empty_never_matches(rng):
    si = jnp.asarray([-1, -1, 3], jnp.int32)
    hi = jnp.asarray([-1, 3, 7], jnp.int32)
    hw = jnp.asarray([0, 5, 2], jnp.int32)
    aw, m = ops.match_weights(si, hi, hw, impl="pallas")
    np.testing.assert_array_equal(np.asarray(aw), [0, 0, 5])
    np.testing.assert_array_equal(np.asarray(m), [False, True, False])


@pytest.mark.parametrize("k,q", [(16, 8), (100, 33), (512, 512), (300, 1000)])
def test_query_pallas_vs_ref(rng, k, q):
    si = rng.integers(-1, 50, k).astype(np.int32)
    sc = (rng.integers(0, 1000, k) * (si != -1)).astype(np.int32)
    se = (rng.integers(0, 50, k) * (si != -1)).astype(np.int32)
    qs = rng.integers(-1, 80, q).astype(np.int32)
    args = tuple(map(jnp.asarray, (si, sc, se, qs)))
    f_p, e_p, m_p = ops.query(*args, impl="pallas")
    f_r, e_r, m_r = query_ref(*args)
    np.testing.assert_array_equal(np.asarray(f_p), np.asarray(f_r))
    np.testing.assert_array_equal(np.asarray(e_p), np.asarray(e_r))
    np.testing.assert_array_equal(np.asarray(m_p), np.asarray(m_r))


def test_auto_impl_dispatches_without_error(rng):
    si, hi, hw = _mk_inputs(rng, 64, 64)
    aw, m = ops.match_weights(si, hi, hw, impl="auto")
    aw_r, _ = match_weights_ref(si, hi, hw)
    np.testing.assert_array_equal(np.asarray(aw), np.asarray(aw_r))


# ---------------------------------------------------------------------------
# Fused ingestion megakernel (ss_ingest) vs the unfused window dispatch
# ---------------------------------------------------------------------------

def _mk_summary_batch(rng, b, k, fill):
    n_fill = int(k * fill)
    items = np.full((b, k), -1, np.int32)
    counts = np.zeros((b, k), np.int32)
    for i in range(b):
        items[i, :n_fill] = rng.choice(8 * k, size=n_fill, replace=False)
        counts[i, :n_fill] = np.sort(
            rng.integers(1, 1000, size=n_fill))[::-1]
    errors = counts // 4
    return tuple(jnp.asarray(a) for a in (items, counts, errors))


INGEST_CASES = [(1, 64, 32), (3, 128, 256), (2, 300, 100)]


@pytest.mark.parametrize("b,k,w", INGEST_CASES)
def test_fused_ingest_kernel_vs_unfused(rng, b, k, w):
    from repro.kernels.ss_ingest import fused_ingest_pallas
    si, sc, se = _mk_summary_batch(rng, b, k, fill=0.6)
    window = jnp.asarray(
        np.minimum(rng.zipf(1.2, size=(b, w)), 8 * k - 1).astype(np.int32))
    out_f = fused_ingest_pallas(si, sc, se, window, interpret=True)
    out_r = ops.ingest_window(si, sc, se, window, impl="sorted")
    for name, a, c in zip(("items", "counts", "errors"), out_f, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c),
                                      err_msg=f"b={b} k={k} w={w} ch={name}")


@pytest.mark.parametrize("b,k", [(1, 64), (4, 256)])
def test_fused_combine_kernel_vs_unfused(rng, b, k):
    from repro.kernels.ss_ingest import fused_combine_pallas
    s1 = _mk_summary_batch(rng, b, k, fill=1.0)
    s2 = _mk_summary_batch(rng, b, k, fill=0.3)
    out_f = fused_combine_pallas(*s1, *s2, interpret=True)
    out_r = ops.combine_summaries(*s1, *s2, impl="sorted")
    for name, a, c in zip(("items", "counts", "errors"), out_f, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c),
                                      err_msg=f"b={b} k={k} ch={name}")


def test_fused_ingest_empty_window_is_top_k_identity(rng):
    """An all-EMPTY window must leave the summary's occupied set intact."""
    si, sc, se = _mk_summary_batch(rng, 2, 128, fill=0.5)
    window = jnp.full((2, 64), -1, jnp.int32)
    out = ops.ingest_window(si, sc, se, window, impl="fused")
    ref = ops.ingest_window(si, sc, se, window, impl="sorted")
    for a, c in zip(out, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


@pytest.mark.parametrize("b,k,w", INGEST_CASES)
def test_ingest_window_sortjoin_vs_other_impls(rng, b, k, w):
    """The two-sort flush equals the dense, sorted and Pallas flushes, on a
    window whose last quarter is EMPTY (a partly filled buffer)."""
    si, sc, se = _mk_summary_batch(rng, b, k, fill=0.6)
    win = np.minimum(rng.zipf(1.2, size=(b, w)), 8 * k - 1).astype(np.int32)
    win[:, 3 * w // 4:] = -1
    window = jnp.asarray(win)
    out = ops.ingest_window(si, sc, se, window, impl="sortjoin")
    for other in ("jnp", "sorted", "pallas"):
        ref = ops.ingest_window(si, sc, se, window, impl=other)
        for name, a, c in zip(("items", "counts", "errors"), out, ref):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(c),
                err_msg=f"sortjoin vs {other} b={b} k={k} w={w} ch={name}")


# ---------------------------------------------------------------------------
# The two-sort merge-join matcher vs the dense and sorted matchers
# ---------------------------------------------------------------------------

MATCH_CASES = ["mixed", "padded", "empty_summary", "all_monitored",
               "none_monitored"]


def _mk_match_case(rng, k, w, case):
    """A summary and a window histogram laid out as ``chunk_histogram``
    emits it: an optional EMPTY slot first, distinct ids ascending, EMPTY
    padding after. Valid ids are distinct on each side."""
    universe = rng.choice(1 << 30, size=k + w, replace=False).astype(np.int32)
    s_ids, fresh = universe[:k], universe[k:]
    s_items = s_ids.copy()
    if case == "empty_summary":
        s_items[:] = -1
    elif case != "all_monitored":
        s_items[rng.random(k) < 0.2] = -1
    n_valid = {"padded": max(1, w // 8)}.get(case, w - 1)
    if case == "all_monitored":
        ids = rng.choice(s_ids, size=min(n_valid, k), replace=False)
    elif case in ("none_monitored", "empty_summary"):
        ids = fresh[:n_valid]
    else:                            # about half of the window monitored
        hot = min(n_valid // 2, k // 2)
        ids = np.concatenate([rng.choice(s_ids, size=hot, replace=False),
                              fresh[:n_valid - hot]])
    h_items = np.full(w, -1, np.int32)
    h_items[1:1 + len(ids)] = np.sort(ids)
    h_weights = (rng.integers(1, w + 1, w) * (h_items != -1)).astype(np.int32)
    return tuple(map(jnp.asarray, (s_items, h_items, h_weights)))


@pytest.mark.parametrize("k,w", [(64, 32), (300, 100), (2000, 16384),
                                 (8000, 16384)])
@pytest.mark.parametrize("case", MATCH_CASES)
def test_sortjoin_match_bitwise_vs_dense_and_sorted(rng, k, w, case):
    args = _mk_match_case(rng, k, w, case)
    out = jax.jit(combine_match_sortjoin)(*args)
    assert out[1] is None
    for name, fn in (("dense", combine_match_ref),
                     ("sorted", combine_match_sorted)):
        ref = jax.jit(fn)(*args)
        for ch, a, c in zip(("add_c", "add_e", "matched_s", "matched_c"),
                            out, ref):
            if c is None:
                assert a is None
                continue
            assert a.dtype == c.dtype and a.shape == c.shape, (ch, name)
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(c),
                err_msg=f"vs {name} k={k} w={w} case={case} out={ch}")
    if case == "all_monitored":
        assert int(np.asarray(out[3]).sum()) == int((args[1] != -1).sum())
    if case in ("none_monitored", "empty_summary"):
        assert not np.asarray(out[2]).any() and not np.asarray(out[3]).any()


@pytest.mark.parametrize("k,c", [(64, 32), (300, 100), (100, 300)])
def test_sortjoin_match_errors_channel(rng, k, c):
    """Summary-vs-summary COMBINE carries errors: the join moves them with
    the counts."""
    si, ci, cc = _mk_match_case(rng, k, c, "mixed")
    ce = cc // 3
    out = combine_match_sortjoin(si, ci, cc, ce)
    ref = combine_match_ref(si, ci, cc, ce)
    for a, b in zip(out, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
