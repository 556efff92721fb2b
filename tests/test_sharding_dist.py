"""Distribution tests that need >1 device: run in subprocesses so the
XLA_FLAGS device-count override never leaks into the main pytest process."""
import os

from conftest import run_distributed as _run

# the runtime matrix honors CI's kernel pin (scaling-smoke / kernel-matrix
# legs run one impl per job); unset, both CPU impls are exercised
_IMPLS = ((os.environ["REPRO_TEST_KERNEL"],)
          if os.environ.get("REPRO_TEST_KERNEL") else ("jnp", "sorted"))


def test_sharded_train_step_matches_single_device():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.registry import get_smoke_arch
from repro.sharding.rules import ShardingPlan
from repro.train import steps as S
from repro.launch.mesh import make_mesh_shape

cfg = get_smoke_arch("qwen2.5-14b")
mesh = make_mesh_shape((2, 4), ("data", "model"))
plan = ShardingPlan(cfg, mesh)
plan0 = ShardingPlan(cfg, None)

key = jax.random.PRNGKey(0)
tokens = jax.random.randint(key, (4, 32), 0, cfg.vocab)
batch = {"tokens": tokens, "labels": tokens}

st_plain = S.init_train_state(cfg, key, plan0)
step_plain = jax.jit(S.make_train_step(cfg, plan0))
st1, m1 = step_plain(st_plain, batch)

st_shard = S.init_train_state(cfg, key, plan)
shardings = S.train_state_shardings(cfg, plan)
st_shard = jax.device_put(st_shard, shardings)
step_shard = jax.jit(S.make_train_step(cfg, plan),
                     in_shardings=(shardings, None),
                     out_shardings=(shardings, None))
st2, m2 = step_shard(st_shard, batch)
d = abs(float(m1["loss"]) - float(m2["loss"]))
assert d < 1e-3, d
# params agree after one step
w1 = np.asarray(st1.params["lm_head"], np.float32)
w2 = np.asarray(jax.device_get(st2.params["lm_head"]), np.float32)
err = np.abs(w1 - w2).max()
assert err < 5e-2, err
print("OK", d, err)
""")
    assert "OK" in out


def test_butterfly_and_hierarchical_reductions_agree():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core import *
from repro.core.spacesaving import pvary_summary
from repro.core.exact import evaluate, overestimation_violations
from repro.launch.mesh import make_mesh_shape

rng = np.random.default_rng(1)
stream = np.minimum(rng.zipf(1.2, 64_000), 10**6).astype(np.int32)
mesh = make_mesh_shape((2, 4), ("pod", "data"))
def f(mode):
    def inner(block):
        s = pvary_summary(init_summary(128), ("pod", "data"))
        s = spacesaving_chunked(s, block[0], chunk_size=1000)
        if mode == "hier":
            s = hierarchical_combine(s, "data", "pod")
        else:
            s = allgather_combine(s, ("pod", "data"))
        return jax.tree.map(lambda x: x[None], s)
    return shard_map(inner, mesh=mesh, in_specs=P(("pod","data")),
                     out_specs=P(("pod","data")))
blocks = jnp.asarray(stream).reshape(8, -1)
for mode in ("hier", "flat"):
    out = f(mode)(blocks)
    s0 = jax.tree.map(lambda a: a[0], out)
    assert overestimation_violations(s0, stream) == 0
    m = evaluate(s0, stream, 64)
    assert m.recall == 1.0 and m.precision == 1.0, m
print("OK")
""")
    assert "OK" in out


def test_stream_runtime_sharded_matches_single_host():
    """The runtime acceptance matrix: sharded ingest+snapshot is bitwise-
    identical to the single-host engine over the same block decomposition,
    for p ∈ {1,2,4,8} × every reduction strategy × kernel impl (pinned by
    REPRO_TEST_KERNEL in CI). hierarchical runs the two-level ("pod",
    "data") topology at p ≥ 4."""
    out = _run(f"""
import jax, jax.numpy as jnp, numpy as np
from repro.core.parallel import block_decompose
from repro.data.synthetic import zipf_stream
from repro.engine import EngineConfig, SketchEngine
from repro.runtime import RuntimeConfig, StreamRuntime

K, LANES, CHUNK, T = 128, 2, 256, 4
stream = jnp.asarray(zipf_stream(30_000, 1.2, seed=0, max_id=10**5))

def single_host(workers, kernel):
    eng = SketchEngine(EngineConfig(k=K, tenants=workers, chunk=CHUNK,
                                    buffer_depth=T, reduction="local",
                                    kernel=kernel))
    st = eng.ingest(eng.init(), block_decompose(stream, workers, CHUNK))
    return eng.snapshot(st)

refs = {{}}
for impl in {_IMPLS!r}:
    for p in (1, 2, 4, 8):
        if (p, impl) not in refs:
            refs[(p, impl)] = single_host(p * LANES, impl)
        ref = refs[(p, impl)]
        for strategy in ("butterfly", "allgather", "hierarchical"):
            pods = 2 if (strategy == "hierarchical" and p >= 4) else 1
            rt = StreamRuntime(RuntimeConfig(
                engine=EngineConfig(k=K, tenants=LANES, chunk=CHUNK,
                                    buffer_depth=T, kernel=impl),
                shards=p, pods=pods, reduction=strategy))
            st = rt.ingest(rt.init(), stream)
            snap = rt.snapshot(st)
            for name, a, b in zip(("items", "counts", "errors"),
                                  snap.summary, ref.summary):
                assert (np.asarray(a) == np.asarray(b)).all(), (
                    impl, p, strategy, name)
            assert int(snap.n) == int(ref.n), (impl, p, strategy)
            assert snap.shard_n.shape == (p * LANES,)

# pre-decomposed blocks whose width is NOT a chunk multiple are rejected
# up front (repeatedly EMPTY-padding a ragged tail INSIDE the pending
# buffer would drift off the canonical decomposition without any visible
# error); padded to the chunk boundary — what host_blocks()/decompose()
# produce — the sharded runtime still matches the single-host engine
# bitwise across flush boundaries, EMPTY-padded partial chunks included
# (the reconstructed fill cursor must ceil-divide; regression test)
p = 2
rt = StreamRuntime(RuntimeConfig(
    engine=EngineConfig(k=K, tenants=LANES, chunk=CHUNK, buffer_depth=T),
    shards=p, reduction="butterfly"))
eng = SketchEngine(EngineConfig(k=K, tenants=p * LANES, chunk=CHUNK,
                                buffer_depth=T, reduction="local"))
odd = jnp.asarray(zipf_stream(p * LANES * 300, 1.2, seed=5,
                              max_id=10**4)).reshape(p * LANES, 300)
try:
    rt.ingest(rt.init(), odd)
    raise SystemExit("expected ValueError for off-chunk blocks")
except ValueError as e:
    assert "multiple of the engine chunk" in str(e), e
pad = jnp.full((p * LANES, 2 * CHUNK - 300), -1, odd.dtype)
padded = jnp.concatenate([odd, pad], axis=1)
st_rt, st_eng = rt.init(), eng.init()
for _ in range(3):                       # cross a flush boundary
    st_rt = rt.ingest(st_rt, padded)
    st_eng = eng.ingest(st_eng, padded)
assert int(st_rt.fill) == int(st_eng.fill), (int(st_rt.fill),
                                             int(st_eng.fill))
for a, b in zip(rt.snapshot(st_rt).summary, eng.snapshot(st_eng).summary):
    assert (np.asarray(a) == np.asarray(b)).all()
print("OK")
""")
    assert "OK" in out


def test_uneven_heads_constraint_compiles():
    out = _run("""
import jax, jax.numpy as jnp
from repro.configs.registry import get_arch
from repro.sharding.rules import ShardingPlan
from repro.launch.mesh import make_mesh_shape
cfg = get_arch("qwen2.5-14b")      # 40 heads — uneven over 8-way model axis
mesh = make_mesh_shape((1, 8), ("data", "model"))
plan = ShardingPlan(cfg, mesh)
def f(x):
    return plan.wsc(x, "bshd") * 2
x = jax.ShapeDtypeStruct((2, 16, 40, 128), jnp.bfloat16)
c = jax.jit(f).lower(x).compile()
print("OK")
""")
    assert "OK" in out


def test_param_spec_resolution():
    from repro.configs.registry import get_arch
    from repro.sharding.rules import ShardingPlan

    class FakeMesh:
        axis_names = ("pod", "data", "model")

        class devices:
            shape = (2, 16, 16)
            size = 512

    cfg = get_arch("qwen1.5-110b")
    plan = ShardingPlan(cfg, None)
    plan.axis_sizes = {"pod": 2, "data": 16, "model": 16}
    plan.has_pod = True
    plan.batch_axes = ("pod", "data")
    # FSDP+TP weight
    spec = plan.param_spec("embed,ff", (8192, 49152))
    assert tuple(spec) == ("data", "model")
    # vocab-parallel embedding
    spec = plan.param_spec("vocab,embed", (152064, 8192))
    assert tuple(spec) == ("model", "data")
    # norm scale replicated
    assert tuple(plan.param_spec("norm", (8192,))) == (None,)
    # non-divisible dim falls back to replicate
    spec = plan.param_spec("ff,embed", (49155, 8192))
    assert tuple(spec) == (None, "data")


def test_moe_param_spec_strategies():
    from repro.configs.registry import get_arch
    from repro.sharding.rules import PlanOptions, ShardingPlan

    cfg = get_arch("qwen3-moe-30b-a3b")
    for strat, want in [("tp", (None, "data", "model")),
                        ("ep", ("model", "data", None))]:
        plan = ShardingPlan(cfg, None, PlanOptions(moe_strategy=strat))
        plan.axis_sizes = {"data": 16, "model": 16}
        spec = plan.param_spec("experts,embed,expert_ff", (128, 2048, 768))
        assert tuple(spec) == want, (strat, tuple(spec))
