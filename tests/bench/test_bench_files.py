"""BENCHMARK.json keeps the contract's characters and every file it names
loads; the copied generator, the exact oracle, the guarantee readings and
the narrower-count control behave as documented."""
import hashlib
import importlib.util
import json
import re

import numpy as np
import pytest

from bench_tiny import REPO  # first: puts the repo root on sys.path

from bench import checks, run, stream
from bench.oracle import Oracle

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
E2E = {m["name"] for m in BENCH["end_to_end"]}


def test_top_level_keys():
    assert list(BENCH) == ["command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (REPO / p).is_dir()
    for word in BENCH["command"]:
        assert LINE.match(word) and not word.startswith("/")


def test_names_units_and_lines():
    named = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
             + BENCH["per_layer"])
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for e in BENCH["configs"] + BENCH["workloads"]:
        assert LINE.match(e["why"])
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    for m in BENCH["per_layer"]:
        assert LINE.match(m["layer"])


def test_every_named_file_loads():
    for c in BENCH["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        for key in ("k", "skew", "n_items", "max_id", "lanes", "shards",
                    "chunk", "guarantees", "assumed"):
            assert key in cfg, (c["name"], key)
        assert cfg["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        mix = json.loads((REPO / "bench" / "traffic" /
                          f"{w['traffic']}.json").read_text())
        assert abs(sum(mix["read_shares"].values()) - 1.0) < 1e-9
        offered = json.loads((REPO / "bench" / "cells" /
                              f"{w['name']}.json").read_text())
        assert offered["write_items_per_s"] > 0
    for m in BENCH["per_layer"]:
        path = run.metric_reader(REPO, m["name"])
        spec = importlib.util.spec_from_file_location("m", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert callable(mod.read)
        assert isinstance(getattr(mod, "SPANS", ()), tuple)
    peaks = json.loads((REPO / "bench" / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_metrics_reach_the_cells_that_report_them():
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"]:
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        assert m["moves"] in E2E
        moved = next(e for e in BENCH["end_to_end"]
                     if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for w in BENCH["workloads"]:
        reported = [m for m in BENCH["end_to_end"]
                    if w["name"] in m.get("workloads", cells)]
        assert any(m["name"] == "setup_s" for m in reported)
        assert len(reported) >= 2
        assert any(w["name"] in m["workloads"] for m in BENCH["per_layer"])


def test_generator_pinned_for_seed_0():
    ids = stream.zipf_stream(100_000, 1.1, seed=0)
    assert ids.dtype == np.int32 and ids.min() >= 1
    assert hashlib.sha256(ids.tobytes()).hexdigest() == (
        "8b63e9ada48af475e1fbb08d6ffdef36f36513f61c9188188e7a03dc58370bcc")


def test_pool_replays_cyclically():
    pool = stream.Pool(np.arange(10, dtype=np.int32), block=4)
    got = np.concatenate([pool.block_at(j) for j in range(5)])
    np.testing.assert_array_equal(got, np.arange(20) % 10)


def test_oracle_equals_brute_force():
    rng = np.random.default_rng(4)
    pool = stream.zipf_stream(5000, 1.3, seed=9, max_id=300)
    oracle = Oracle(pool)
    ids = np.concatenate([rng.choice(pool, 40), [10**6]])
    for n in (0, 1, 2500, 5000, 12_345):
        seq = np.resize(pool, n)
        want = np.array([(seq == x).sum() for x in ids])
        np.testing.assert_array_equal(oracle.counts(ids, n), want)
        thr = max(1, n // 50)
        heavy, c = oracle.heavy(n, thr)
        u, uc = np.unique(seq, return_counts=True)
        np.testing.assert_array_equal(np.sort(heavy), u[uc >= thr])
        top, tc = oracle.top(n, 7)
        assert list(tc[tc > 0]) == sorted(uc, reverse=True)[:7]


def _spacesaving(seq, k):
    """Sequential Space Saving, plain Python: the textbook summary."""
    counts, errors = {}, {}
    for x in seq.tolist():
        if x in counts:
            counts[x] += 1
        elif len(counts) < k:
            counts[x], errors[x] = 1, 0
        else:
            y = min(counts, key=counts.get)
            m = counts.pop(y)
            errors.pop(y)
            counts[x], errors[x] = m + 1, m
    items = np.array(list(counts), np.int64)
    return (items, np.array([counts[i] for i in items]),
            np.array([errors[i] for i in items]))


def test_readings_of_a_sound_summary_are_zero():
    pool = stream.zipf_stream(20_000, 1.1, seed=5, max_id=5000)
    n, k = 30_000, 50
    items, counts, errors = _spacesaving(np.resize(pool, n), k)
    r = checks.check_summary(Oracle(pool), items, counts, errors, n=n,
                             acked=n, k=k)
    assert r == dict.fromkeys(r, 0)


@pytest.mark.parametrize("fault,reading", [
    ("under", "underestimated"), ("lower", "lower_above_true"),
    ("over", "over_n_per_k"), ("eps", "error_over_n_per_k"),
    ("drop", "heavy_missing"), ("lost", "items_lost")])
def test_each_guarantee_has_its_reading(fault, reading):
    pool = stream.zipf_stream(20_000, 1.1, seed=5, max_id=5000)
    n, k = 30_000, 50
    oracle = Oracle(pool)
    items, counts, errors = _spacesaving(np.resize(pool, n), k)
    top = int(np.argmax(counts))
    acked = n
    if fault == "under":
        counts[top] -= errors[top] + 1
    elif fault == "lower":
        errors[top] = -1
    elif fault == "over":
        counts[top] += n // k + 1
        errors[top] += n // k + 1
    elif fault == "eps":
        errors[top] = n // k + 1
    elif fault == "drop":
        items[top] = checks.EMPTY
    else:
        acked = n + 16
    r = checks.check_summary(oracle, items, counts, errors, n=n,
                             acked=acked, k=k)
    assert r[reading] > 0 and not checks.verdict(r)


def test_read_answers_are_judged_at_their_n():
    pool = stream.zipf_stream(20_000, 1.1, seed=5, max_id=5000)
    oracle = Oracle(pool)
    ids = np.array([1, 2, 3])
    f = oracle.counts(ids, 10_000)
    ok = {"n": 10_000, "ids": ids, "f_hat": f + 2, "lower": f - 1}
    assert not checks.read_is_wrong(oracle, "point", ok)
    assert checks.read_is_wrong(oracle, "point", {**ok, "f_hat": f - 1})
    heavy, _ = oracle.heavy(10_000, 10_000 // 50 + 1)
    rep = {"n": 10_000, "threshold": 10_000 // 50 + 1,
           "candidates": heavy, "guaranteed": heavy[:1]}
    assert not checks.read_is_wrong(oracle, "kmaj", rep)
    assert checks.read_is_wrong(oracle, "kmaj", {**rep,
                                                 "candidates": heavy[1:]})
    assert checks.read_is_wrong(oracle, "kmaj", {**rep,
                                                 "guaranteed": [10**7]})


def _answers(items, counts, errors, n, k, ids):
    """The three reads of one summary, worked out by hand."""
    slot = {int(x): i for i, x in enumerate(items)}
    m = int(counts.min()) if len(items) == k else 0
    order = np.argsort(-counts, kind="stable")[:10]
    thr = n // k + 1
    return {
        "point": {"n": n, "ids": ids,
                  "f_hat": np.array([counts[slot[x]] if x in slot else m
                                     for x in ids.tolist()]),
                  "lower": np.array([counts[slot[x]] - errors[slot[x]]
                                     if x in slot else 0
                                     for x in ids.tolist()])},
        "top": {"n": n, "asked": 10, "items": items[order],
                "counts": counts[order],
                "lower": counts[order] - errors[order]},
        "kmaj": {"n": n, "threshold": thr,
                 "candidates": items[counts >= thr][::-1],
                 "guaranteed": items[counts - errors >= thr]},
    }


def _reverse_top(a):
    a["top"] = {**a["top"], **{c: a["top"][c][::-1]
                               for c in ("items", "counts", "lower")}}


def _drop_guaranteed(a):
    a["kmaj"] = {**a["kmaj"], "guaranteed": a["kmaj"]["guaranteed"][:0]}


def _shift_point(a):
    a["point"] = {**a["point"], "f_hat": a["point"]["f_hat"] + 1}


def _swap_lower(a):
    a["point"] = {**a["point"], "lower": a["point"]["lower"][::-1]}


def _loose_top(a):
    a["top"] = {**a["top"], "items": a["top"]["items"][1:],
                "counts": a["top"]["counts"][1:],
                "lower": a["top"]["lower"][1:]}


def _all_candidates(a, items):
    a["kmaj"] = {**a["kmaj"], "candidates": items}


def _other_n(a):
    a["kmaj"] = {**a["kmaj"], "n": a["kmaj"]["n"] + 1}


@pytest.mark.parametrize("alter,op", [
    (_reverse_top, "top"), (_loose_top, "top"), (_drop_guaranteed, "kmaj"),
    (_all_candidates, "kmaj"), (_other_n, "kmaj"), (_shift_point, "point"),
    (_swap_lower, "point")])
def test_reads_recomputed_from_their_summary(alter, op):
    """Each read is exactly what its version's summary answers; any
    departure reads as a mismatch, also one the guarantees let pass."""
    pool = stream.zipf_stream(20_000, 1.1, seed=5, max_id=5000)
    n, k = 30_000, 50
    items, counts, errors = _spacesaving(np.resize(pool, n), k)
    summary = {"items": items, "counts": counts, "errors": errors, "n": n}
    ids = np.concatenate([items[:5], [10**6, 10**6 + 1]])
    sound = _answers(items, counts, errors, n, k, ids)
    for name, ans in sound.items():
        assert not checks.read_differs(name, ans, summary, k=k), name
    altered = dict(sound)
    if alter is _all_candidates:
        alter(altered, items)
    else:
        alter(altered)
    assert checks.read_differs(op, altered[op], summary, k=k)


def test_control_fails_where_the_reference_holds():
    """The exact top-k at int32 keeps every guarantee; at int16, the
    control, its heavy counts wrap and read as underestimates."""
    pool = stream.zipf_stream(400_000, 1.1, seed=11)
    oracle = Oracle(pool)
    n, k = 700_000, 200
    ref = checks.check_summary(
        oracle, *checks.control_summary(oracle, n=n, k=k, dtype=np.int32),
        n=n, acked=n, k=k)
    assert checks.verdict(ref), ref
    ctl = checks.check_summary(
        oracle, *checks.control_summary(oracle, n=n, k=k, dtype=np.int16),
        n=n, acked=n, k=k)
    assert ctl["underestimated"] > 0 and not checks.verdict(ctl)
