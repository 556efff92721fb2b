"""The open-loop schedules and the arithmetic on their stamps, on known
samples."""
import bench_tiny  # noqa: F401 (puts the repo root on sys.path)
import numpy as np
import pytest

from bench import schedule


def test_poisson_due_same_gaps_any_seed():
    a = schedule.poisson_due(500.0, 4.0, np.random.default_rng(1))
    b = schedule.poisson_due(500.0, 4.0, np.random.default_rng(2**33 + 5))
    assert a.size == b.size == 2000
    assert a[0] == b[0] == 0.0
    assert np.all(np.diff(a) > 0)
    # the same set of gaps in another order
    ga = np.sort(np.diff(np.append(a, 4.0)))
    gb = np.sort(np.diff(np.append(b, 4.0)))
    np.testing.assert_allclose(ga, gb, rtol=0, atol=1e-12)
    assert not np.array_equal(a, b)
    assert np.diff(np.append(a, 4.0)).sum() == pytest.approx(4.0)
    # exponential gaps: the mean is 1/rate, the spread about as large
    gaps = np.diff(np.append(a, 4.0))
    assert gaps.mean() == pytest.approx(1 / 500.0)
    assert gaps.std() == pytest.approx(1 / 500.0, rel=0.1)


def test_poisson_due_empty():
    assert schedule.poisson_due(0.1, 1.0, np.random.default_rng(0)).size == 0


def test_shuffled_mix_fixed_shares():
    rng = np.random.default_rng(3)
    ops = schedule.shuffled_mix({"point": 0.7, "top": 0.2, "kmaj": 0.1},
                                1001, rng)
    assert len(ops) == 1001
    assert (ops.count("point"), ops.count("top"), ops.count("kmaj")) == (
        701, 200, 100)
    assert ops[:20] != sorted(ops[:20], key=["point", "top", "kmaj"].index)


@pytest.mark.parametrize("q,want", [(50, 50.0), (99, 99.0), (100, 100.0),
                                    (1, 1.0), (99.5, 100.0)])
def test_percentile_nearest_rank(q, want):
    v = np.arange(100, 0, -1).astype(float)    # 1..100, unsorted
    assert schedule.percentile(v, q) == want


def test_percentile_counts_misses_as_infinite():
    v = np.array([1.0] * 98 + [np.inf] * 2)
    assert schedule.percentile(v, 98) == 1.0
    assert schedule.percentile(v, 99) == np.inf
    assert np.isnan(schedule.percentile([], 99))


def test_freshness_from_synthetic_stamps():
    due = np.array([0.0, 0.1, 0.2, 0.3])
    ends = np.array([10, 20, 30, 40])
    stamp_t = np.array([0.05, 0.25, 0.26, 0.5])
    stamp_n = np.array([10, 30, 20, 30])       # a late, older stamp
    f = schedule.freshness(due, ends, stamp_t, stamp_n)
    np.testing.assert_allclose(f[:3], [0.05, 0.15, 0.05])
    assert f[3] == np.inf                      # never covered


def test_count_at_is_newest_reach():
    t = np.array([1.0, 2.0, 3.0])
    n = np.array([5, 9, 7])
    assert schedule.count_at(0.5, t, n) == 0
    assert schedule.count_at(2.0, t, n) == 9
    assert schedule.count_at(3.5, t, n) == 9


def test_own_lateness_excludes_backpressure():
    due = np.array([0.0, 1.0, 2.0, 3.0])
    prev_ret = np.array([-np.inf, 0.5, 2.8, 3.1])
    call = np.array([0.01, 1.0, 2.8, 3.3])
    late = schedule.own_lateness(due, call, prev_ret)
    np.testing.assert_allclose(late, [0.01, 0.0, 0.0, 0.2])
