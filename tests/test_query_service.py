"""QueryService: query-kernel parity matrix, snapshots, frontend, eval.

The read-side mirror of tests/test_merge_core.py (DESIGN.md §7):
  * jnp / sorted / pallas query kernels are bitwise-identical across k,
    query mixes and summary fill levels;
  * snapshots are pure (no state mutation, no buffer flush), versioned,
    and equal to the engine's merged view;
  * the frontend's estimates respect lower ≤ f ≤ f̂ against the exact
    oracle, top/prune edge cases (n > k, empty summary, n = 0) are
    guarded, and the k-majority report's guaranteed split is sound;
  * the accuracy harness upholds the paper's invariants and its CI gate
    actually fires on a corrupted record.

``REPRO_TEST_KERNEL`` restricts the impl sweep (CI's kernel-matrix leg
runs one impl per job); unset, all three are exercised.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EMPTY, init_summary, prune, update_chunk
from repro.core.exact import exact_counts, true_heavy_hitters
from repro.engine import EngineConfig, SketchEngine
from repro.kernels import ops
from repro.kernels.ref import query_ref
from repro.service import QueryFrontend, publish

ALL_IMPLS = ("jnp", "sorted", "pallas")
IMPLS = ((os.environ["REPRO_TEST_KERNEL"],)
         if os.environ.get("REPRO_TEST_KERNEL") else ALL_IMPLS)


def zipf(n, skew=1.2, seed=0, cap=10**6):
    r = np.random.default_rng(seed)
    return ((r.zipf(skew, n) - 1) % cap + 1).astype(np.int32)


def _summary_at_fill(k, fill, seed):
    """A summary with ~fill·k occupied counters (0.0 → empty, 1.0 → full)."""
    if fill == 0.0:
        return init_summary(k)
    n = max(int(2.5 * k * fill), 1)
    distinct_cap = max(int(k * fill), 1)
    stream = zipf(n, seed=seed) % distinct_cap
    return update_chunk(init_summary(k), jnp.asarray(stream))


def _query_mix(s, seed, n_each=12):
    """Monitored ids + certainly-absent ids + EMPTY padding probes."""
    items = np.asarray(s.items)
    monitored = items[items != EMPTY][:n_each]
    absent = 10**7 + np.arange(n_each, dtype=np.int32)
    return jnp.asarray(np.concatenate(
        [monitored, absent, np.full(3, EMPTY, np.int32)]).astype(np.int32))


def _ingested_engine(k=128, tenants=4, kernel="jnp", n=20_000, skew=1.1,
                     seed=0, chunk=512, depth=2):
    stream = zipf(n, skew=skew, seed=seed)
    engine = SketchEngine(EngineConfig(k=k, tenants=tenants, chunk=chunk,
                                       buffer_depth=depth, kernel=kernel))
    per = -(-n // tenants)
    padded = np.full(per * tenants, EMPTY, np.int32)
    padded[:n] = stream
    state = engine.ingest(engine.init(),
                          jnp.asarray(padded.reshape(tenants, per)))
    return engine, state, stream


# ---------------------------------------------------------------------------
# Query-path kernel matrix (mirrors the COMBINE matrix of test_merge_core)
# ---------------------------------------------------------------------------

@pytest.mark.kernel_matrix
@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("k", [16, 300, 1024])
@pytest.mark.parametrize("fill", [0.0, 0.4, 1.0])
def test_query_impls_bitwise_equal(impl, k, fill):
    s = _summary_at_fill(k, fill, seed=k)
    q = _query_mix(s, seed=k)
    ref = query_ref(s.items, s.counts, s.errors, q)
    out = ops.query(s.items, s.counts, s.errors, q, impl=impl)
    for name, a, b in zip(("f_hat", "eps", "monitored"), ref, out):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"impl={impl} k={k} fill={fill} out={name}")


@pytest.mark.parametrize("k", [16, 1024])
def test_query_auto_matches_explicit_ref(k):
    """'auto' dispatch (jnp small-k / sorted large-k on CPU) stays bitwise."""
    s = _summary_at_fill(k, 0.8, seed=k + 7)
    q = _query_mix(s, seed=k)
    ref = query_ref(s.items, s.counts, s.errors, q)
    out = ops.query(s.items, s.counts, s.errors, q, impl="auto")
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_query_wide_dtype_never_hits_pallas():
    """int64 counts route to the exact sorted path instead of truncating."""
    s = _summary_at_fill(64, 1.0, seed=3)
    q = _query_mix(s, seed=3)
    with jax.enable_x64():
        big = s.counts.astype(jnp.int64) + jnp.asarray(2**33, jnp.int64)
        f, eps, mon = ops.query(s.items, big, s.errors.astype(jnp.int64),
                                q, impl="pallas")
        monitored = np.asarray(mon)
        assert (np.asarray(f)[monitored] > 2**33).all()


# ---------------------------------------------------------------------------
# Snapshot semantics: pure, versioned, consistent
# ---------------------------------------------------------------------------

def test_snapshot_is_pure_and_versioned():
    engine, state, _ = _ingested_engine()
    buf = np.asarray(state.buffer).copy()
    fill = int(state.fill)
    snap1 = engine.snapshot(state)
    snap2 = engine.snapshot(state)
    # no flush, no mutation: buffer and fill untouched
    np.testing.assert_array_equal(buf, np.asarray(state.buffer))
    assert int(state.fill) == fill
    # versions are monotonic per engine; same state → same arrays
    assert snap2.version == snap1.version + 1
    np.testing.assert_array_equal(np.asarray(snap1.summary.counts),
                                  np.asarray(snap2.summary.counts))


def test_snapshot_matches_merged_and_counts_pending():
    engine, state, stream = _ingested_engine(n=10_240, chunk=512, depth=4)
    snap = engine.snapshot(state)
    merged = engine.merged(state)
    for a, b in zip(snap.summary, merged):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # total n covers every ingested item, including still-buffered ones
    assert int(snap.n) == len(stream)
    assert snap.tenants == 4 and np.asarray(snap.shard_n).sum() == len(stream)


def test_snapshot_immutable_under_continued_ingest():
    engine, state, _ = _ingested_engine(n=8_000)
    snap = engine.snapshot(state)
    before = np.asarray(snap.summary.counts).copy()
    n_before = int(snap.n)
    state = engine.ingest(state, jnp.asarray(
        zipf(4 * 512, seed=99).reshape(4, -1)))
    snap2 = engine.snapshot(state)
    # the old snapshot still answers from its frozen view
    np.testing.assert_array_equal(before, np.asarray(snap.summary.counts))
    assert int(snap.n) == n_before
    assert int(snap2.n) == n_before + 4 * 512
    assert snap2.version > snap.version


# ---------------------------------------------------------------------------
# Frontend: estimates, planning, top/threshold guards
# ---------------------------------------------------------------------------

@pytest.mark.kernel_matrix
@pytest.mark.parametrize("impl", IMPLS)
def test_estimate_bounds_vs_oracle(impl):
    engine, state, stream = _ingested_engine(kernel=impl)
    snap = engine.snapshot(state)
    fe = QueryFrontend(impl)
    exact = exact_counts(stream)
    queries = list(exact)[:40] + [10**7, 10**7 + 1]
    f_hat, lower, mon = (np.asarray(x)
                         for x in fe.estimate(snap, queries))
    for i, item in enumerate(queries):
        f = exact.get(item, 0)
        assert lower[i] <= f <= f_hat[i], (item, lower[i], f, f_hat[i])


def test_estimate_many_matches_single_calls():
    engine, state, _ = _ingested_engine()
    snap = engine.snapshot(state)
    fe = QueryFrontend("jnp")
    sets = [[1, 2, 3], [5], list(range(1, 20))]
    batched = fe.estimate_many(snap, sets)
    for qs, (f_b, lo_b, mon_b) in zip(sets, batched):
        f_s, lo_s, mon_s = fe.estimate(snap, qs)
        np.testing.assert_array_equal(np.asarray(f_b), np.asarray(f_s))
        np.testing.assert_array_equal(np.asarray(lo_b), np.asarray(lo_s))
        np.testing.assert_array_equal(np.asarray(mon_b), np.asarray(mon_s))


def test_plan_buckets_bound_retraces():
    fe = QueryFrontend("jnp", min_batch=16)
    for q, want in ((1, 16), (16, 16), (17, 32), (100, 128)):
        padded, sizes = fe.plan(jnp.zeros((q,), jnp.int32))
        assert padded.shape[0] == want and sizes == [q]
    # padding is EMPTY → reported unmonitored, dropped on unpad
    padded, _ = fe.plan(jnp.asarray([3], jnp.int32))
    assert (np.asarray(padded)[1:] == EMPTY).all()


def test_top_guards_n_beyond_k_and_empty():
    engine, state, _ = _ingested_engine(k=64)
    snap = engine.snapshot(state)
    fe = QueryFrontend("jnp")
    items, counts = fe.top(snap, 10_000)          # n > k → clamped to k
    assert items.shape == (64,) == counts.shape
    items, counts = fe.top(snap, 0)               # n = 0 → empty
    assert items.shape == (0,)
    items, counts = fe.top(snap, -3)              # negative → empty, no wrap
    assert items.shape == (0,)
    # engine.top carries the same guard
    items, counts = engine.top(state, n=10_000)
    assert items.shape == (64,)
    # fully-empty summary (all EMPTY sentinels): table is empty, not fake
    empty_snap = engine.snapshot(engine.init())
    assert fe.top_table(empty_snap, 5) == []
    assert int(empty_snap.n) == 0 and int(empty_snap.occupancy) == 0


def test_threshold_scan():
    engine, state, stream = _ingested_engine()
    snap = engine.snapshot(state)
    fe = QueryFrontend("jnp")
    items, counts = fe.threshold(snap, 100)
    assert (counts >= 100).all()
    assert (np.diff(counts) <= 0).all()           # count-descending
    s_counts = np.asarray(snap.summary.counts)
    s_items = np.asarray(snap.summary.items)
    want = ((s_items != EMPTY) & (s_counts >= 100)).sum()
    assert items.size == want


# ---------------------------------------------------------------------------
# prune / k-majority report edge cases and soundness
# ---------------------------------------------------------------------------

def test_prune_edge_cases():
    s = init_summary(32)
    items, counts, cand, guaranteed = prune(s, 0, 8)   # n=0, empty summary
    assert not np.asarray(cand).any() and not np.asarray(guaranteed).any()
    with pytest.raises(ValueError):
        prune(s, 100, 0)
    with pytest.raises(ValueError):
        prune(s, 100, -2)


def test_k_majority_report_sound_vs_oracle():
    engine, state, stream = _ingested_engine(k=128, n=30_000)
    snap = engine.snapshot(state)
    fe = QueryFrontend("jnp")
    rep = fe.k_majority_report(snap, 128)
    exact = exact_counts(stream)
    truth = true_heavy_hitters(stream, 128)
    # guaranteed ⇒ truly k-majority (zero false positives by construction)
    for g in rep.guaranteed_items:
        assert exact.get(int(g), 0) >= rep.threshold, int(g)
    # containment: every true k-majority item is somewhere in the candidates
    cand = set(int(i) for i in rep.candidate_items)
    for t in truth:
        assert t in cand, t
    # split is a partition of the candidate set
    assert not (set(map(int, rep.guaranteed_items))
                & set(map(int, rep.unconfirmed_items)))
    assert rep.complete and rep.version == snap.version


def test_k_majority_report_degenerate_inputs():
    engine = SketchEngine(EngineConfig(k=16, tenants=1, chunk=8,
                                       buffer_depth=1))
    fe = QueryFrontend("jnp")
    snap = engine.snapshot(engine.init())          # n = 0, all-EMPTY
    rep = fe.k_majority_report(snap, 4)
    assert rep.n == 0 and rep.threshold == 1
    assert rep.guaranteed_items.size == 0 and rep.unconfirmed_items.size == 0
    with pytest.raises(ValueError):
        fe.k_majority_report(snap, 0)
    # k_majority beyond the counter budget: report flags incompleteness
    assert not fe.k_majority_report(snap, 64).complete


def test_publish_from_bare_summary():
    s = update_chunk(init_summary(32), jnp.asarray(zipf(500, seed=5)))
    snap = publish(s, 500, [500], version=7, kernel="jnp")
    assert snap.version == 7 and snap.tenants == 1 and snap.k == 32
    fe = QueryFrontend("jnp")
    assert fe.top_table(snap, 3)


# ---------------------------------------------------------------------------
# Accuracy harness: the paper's invariants + the CI gate actually fires
# ---------------------------------------------------------------------------

@pytest.mark.kernel_matrix
@pytest.mark.parametrize("impl", IMPLS)
def test_eval_cell_upholds_paper_invariants(impl):
    from repro.eval.accuracy import evaluate_cell
    cell = evaluate_cell(n=20_000, skew=1.1, k=128, impl=impl, seed=1,
                         max_id=10**5)
    assert cell["guaranteed_recall"] == 1.0
    assert cell["recall"] == 1.0
    assert cell["bound_violations"] == 0
    assert cell["k_majority"] == 128        # tight default: k_majority = k


def test_eval_sweep_record_shape_and_check():
    from repro.eval.accuracy import check_record, run_sweep
    rows = []
    rec = run_sweep(n=8_000, skews=(1.5,), ks=(64,), impls=("jnp", "sorted"),
                    max_id=10**4, emit=lambda *a: rows.append(a))
    assert len(rec["cells"]) == 2 and len(rows) == 2
    assert rec["summary"]["min_guaranteed_recall"] == 1.0
    assert check_record(rec) == []
    # the gate fires on a corrupted record — the CI leg is not a tautology
    bad = {"cells": [dict(rec["cells"][0], guaranteed_recall=0.5),
                     dict(rec["cells"][1], recall=0.9)]}
    failures = check_record(bad)
    assert len(failures) == 2
    assert any("guaranteed_recall" in f for f in failures)
    assert any("containment" in f for f in failures)
