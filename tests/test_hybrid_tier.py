"""The paper's hybrid deployment through the served path: a ServingTier over
4 shards × 8 lanes, whose publish is two programs (the lane reduce, then
the exchange between shards). Everything sharded runs once, in one
subprocess with 4 forced host devices; the tests below read its report."""
import json
from pathlib import Path

import numpy as np
import pytest

from conftest import run_distributed

REPO = Path(__file__).resolve().parents[1]
STRATEGIES = [("butterfly", 1), ("allgather", 1), ("hierarchical", 2)]

SNIPPET = """
import json, sys
sys.path.insert(0, {repo!r})
import jax, numpy as np
from jax.sharding import PartitionSpec as P
from bench import checks, stream
from bench.oracle import Oracle
from repro.core.spacesaving import Summary
from repro.engine import EngineConfig, SketchEngine
from repro.engine.state import SketchState
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer
from repro.runtime import RuntimeConfig, StreamRuntime
from repro.runtime.feed import host_blocks
from repro.serve import ServeConfig, ServingTier

K, LANES, CHUNK, DEPTH, SHARDS = 64, 8, 128, 2, 4
ENGINE = EngineConfig(k=K, tenants=LANES, chunk=CHUNK, buffer_depth=DEPTH)
out = {{}}

def equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))

def one_program(rt):
    # the publish as it was: flush view, lane reduce and mesh reduction
    # in one shard_map program
    spec = P(rt._dim0)
    rows = Summary(spec, spec, spec)
    def body(summary, buffer, n, fill):
        st = SketchState(summary=summary, buffer=buffer, fill=fill, n=n)
        return jax.tree.map(lambda a: a[None], rt.engine._merged(st))
    f = jax.shard_map(body, mesh=rt.mesh,
                      in_specs=(rows, spec, spec, P()), out_specs=rows,
                      check_vma=False)
    return jax.jit(lambda s: jax.tree.map(
        lambda a: a[0], f(s.summary, s.buffer, s.n, s.fill)))

ids = stream.zipf_stream(40_000, 1.1, seed=5, max_id=5000)
for name, pods in {strategies!r}:
    rt = StreamRuntime(RuntimeConfig(engine=ENGINE, shards=SHARDS,
                                     pods=pods, reduction=name))
    ref = one_program(rt)
    state, same = rt.init(), []
    # 3 chunks a worker: the buffer is part full (the flush view's
    # branch), then 4 more: full and flushed (the summaries' branch)
    for n_chunks in (3, 4):
        per = n_chunks * CHUNK
        state = rt.ingest(state, ids[:rt.workers * per].reshape(
            rt.workers, per))
        same.append(equal(rt.merged(state), ref(state)))
    out["bitwise_" + name] = same

# the tier: 4 shards x 8 lanes fed through submit, every launch spied
rt = StreamRuntime(RuntimeConfig(engine=ENGINE, shards=SHARDS,
                                 reduction="auto"))
tracer, registry = Tracer(capacity=100_000), MetricsRegistry()
launched = []
def spy(kind, fn):
    def call(*args, **kw):
        stack = tracer._stack()
        launched.append((kind, stack[-1] if stack else 0))
        return fn(*args, **kw)
    return call
rt._lane_reduce_fn = spy("lane_reduce", rt._lane_reduce_fn)
rt._exchange_fn = spy("exchange", rt._exchange_fn)
pool = stream.Pool(ids, rt.workers * CHUNK)
blocks = [pool.block_at(j) for j in range(13)]
cfg = ServeConfig(runtime=rt.config, publish_every=3, ring_depth=4,
                  lazy_publish=False, flight_recorder=False)
compiled = []
def on_compile(event, secs, fun_name="", **kw):
    if event == "/jax/core/compile/backend_compile_duration":
        compiled.append(fun_name)
with ServingTier(cfg, runtime=rt, registry=registry,
                 tracer=tracer) as tier:
    # start() has compiled every group shape; what compiles from here on
    # is the publish's programs alone
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    for b in blocks:
        tier.submit(b)
    snap = tier.drain(timeout=120)
out["compiled_while_serving"] = list(compiled)
out["groups"] = registry.histogram("serve.ingest.coalesce_blocks").describe()
out["reduction"] = rt.engine.config.reduction
out["snapshot_devices"] = sorted({{d.id for a in (*snap.summary, snap.n)
                                  for d in a.devices()}})
out["rank0_device"] = rt.mesh.devices.flat[0].id
out["publishes"] = tier.stats.publishes      # the stop's drain included
out["exchanges"] = registry.counter("serve.publish.exchanges").value
spans = {{e["id"]: e for e in tracer.events() if e["kind"] == "span"}}
out["launches"] = [
    [kind, spans[sid]["name"],
     spans.get(spans[sid]["parent"], {{}}).get("name")]
    for kind, sid in launched]

# the same blocks, canonically decomposed, into one device's 32 tenants
eng = SketchEngine(EngineConfig(k=K, tenants=SHARDS * LANES, chunk=CHUNK,
                                buffer_depth=DEPTH))
state = eng.init()
for b in blocks:
    state = eng.ingest(state, host_blocks(b, SHARDS * LANES, CHUNK))
out["bitwise_engine"] = equal(snap.summary, eng.merged(state))
n = int(snap.n)
out["n"], out["acked"] = n, len(blocks) * pool.block
out["readings"] = checks.check_summary(
    Oracle(ids), *snap.summary, n=n, acked=out["acked"], k=K)
print("REPORT", json.dumps(out))
"""


@pytest.fixture(scope="module")
def report():
    text = run_distributed(SNIPPET.format(repo=str(REPO),
                                          strategies=STRATEGIES), n_dev=4)
    line = next(ln for ln in text.splitlines() if ln.startswith("REPORT "))
    return json.loads(line[len("REPORT "):])


def test_four_shard_tier_equals_one_device_engine_and_exact_counts(report):
    """The drained snapshot of 4 shards × 8 lanes is bitwise the summary
    of one device's 32 tenants fed the canonical decomposition, and keeps
    every guarantee against the exact counts."""
    assert report["reduction"] == "butterfly"
    assert report["bitwise_engine"] is True
    assert report["n"] == report["acked"]
    assert report["readings"] == dict.fromkeys(report["readings"], 0)


def test_published_snapshot_lives_on_rank_0s_device(report):
    """The summary and n of a four-shard publish are rank 0's buffers, so
    the reads' kernels run on one device (a Mosaic kernel cannot be
    partitioned over the mesh)."""
    assert report["snapshot_devices"] == [report["rank0_device"]]


@pytest.mark.parametrize("strategy", [s for s, _ in STRATEGIES])
def test_lane_reduce_then_exchange_equals_one_program(report, strategy):
    """Part-full buffer, then a flushed one: the two publish programs give
    the bits the one-program publish gave."""
    assert report["bitwise_" + strategy] == [True, True]


def test_exchange_launched_inside_its_span_inside_publish(report):
    launches = report["launches"]
    assert {kind for kind, _, _ in launches} == {"lane_reduce", "exchange"}
    for kind, span, parent in launches:
        if kind == "exchange":
            assert (span, parent) == ("ingest.exchange", "ingest.publish")
        else:
            assert span == "ingest.publish"


def test_four_shard_tier_compiles_no_ingest_while_serving(report):
    """The mesh tier's start() compiles the sharded ingest program for
    every group shape (widths 1 and 2 at publish_every 3): serving the 13
    blocks compiles none, whatever groups the drains make."""
    assert report["groups"]["sum"] == 13
    assert report["groups"]["max"] <= 2
    assert not [n for n in report["compiled_while_serving"]
                if "ingest" in n], report["compiled_while_serving"]
    assert report["compiled_while_serving"]     # the publish's programs


def test_exchanges_counted_once_per_publish_on_four_shards(report):
    exchanges = [k for k, _, _ in report["launches"] if k == "exchange"]
    assert report["publishes"] > 1
    assert report["exchanges"] == report["publishes"] == len(exchanges)


def test_one_shard_tier_has_no_exchange():
    """One shard keeps its one publish program: no exchange is built or
    launched, no ``ingest.exchange`` span opens, the counter reads 0."""
    from repro.engine import EngineConfig
    from repro.obs.metrics import MetricsRegistry
    from repro.obs.trace import Tracer
    from repro.runtime import RuntimeConfig, StreamRuntime
    from repro.serve import ServeConfig, ServingTier

    rt = StreamRuntime(RuntimeConfig(
        engine=EngineConfig(k=32, tenants=2, chunk=64, buffer_depth=2),
        shards=1))
    assert not hasattr(rt, "_exchange_fn")
    tracer, registry = Tracer(capacity=10_000), MetricsRegistry()
    cfg = ServeConfig(runtime=rt.config, publish_every=2, lazy_publish=False,
                      flight_recorder=False)
    rng = np.random.default_rng(3)
    with ServingTier(cfg, runtime=rt, registry=registry,
                     tracer=tracer) as tier:
        for _ in range(5):
            tier.submit(rng.integers(1, 500, rt.workers * 64,
                                     dtype=np.int32))
        tier.drain(timeout=60)
        assert tier.stats.publishes > 1
    names = {e["name"] for e in tracer.events() if e["kind"] == "span"}
    assert "ingest.publish" in names and "ingest.exchange" not in names
    assert registry.counter("serve.publish.exchanges").value == 0
