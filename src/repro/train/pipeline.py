"""Pipeline parallelism (GPipe schedule) over a dedicated ``pipe`` mesh axis.

For deployments beyond TP×FSDP reach (>512 chips or cross-slice), layers
are grouped into S stages laid out on the ``pipe`` axis; microbatches flow
stage-to-stage via ``lax.ppermute`` inside ``jax.shard_map``. The schedule
is the classic (S + M - 1)-tick GPipe loop:

    tick t: stage s computes microbatch (t - s) if 0 ≤ t - s < M,
            then hands its activation to stage s+1.

Bubble fraction = (S-1)/(M+S-1); choose M ≫ S. Differentiating through the
loop works out of the box (ppermute's transpose is the reverse permute), so
``jax.grad`` of a pipelined loss is the 1F1B-equivalent backward at GPipe
memory cost. This module is mesh-composable: the per-stage ``stage_fn`` can
itself be pjit-sharded over (data, model) — the pipe axis only moves
activations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, x, *, mesh, n_micro: int,
                   axis: str = "pipe"):
    """Run ``x`` through S pipelined stages.

    stage_params: pytree with leading dim S (sharded over ``axis``).
    x: (M, mb, ...) microbatched input (replicated; only stage 0 reads it).
    stage_fn(params_slice, activation) -> activation, same shape/dtype.
    Returns (M, mb, ...) outputs (valid on the LAST stage; replicated back).
    """
    n_stages = mesh.shape[axis]

    def per_stage(params_local, xs):
        params_local = jax.tree.map(lambda a: a[0], params_local)
        s = lax.axis_index(axis)
        m = xs.shape[0]
        ticks = m + n_stages - 1

        buf0 = lax.pcast(jnp.zeros_like(xs[0]), (axis,), to="varying")
        out0 = lax.pcast(jnp.zeros_like(xs), (axis,), to="varying")
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            recv, outs = carry
            mb_idx = t - s
            active = (mb_idx >= 0) & (mb_idx < m)
            x_in = jnp.where(s == 0,
                             xs[jnp.clip(mb_idx, 0, m - 1)], recv)
            y = stage_fn(params_local, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage writes its finished microbatch
            outs = jnp.where(
                active & (s == n_stages - 1),
                lax.dynamic_update_index_in_dim(
                    outs, y, jnp.clip(mb_idx, 0, m - 1), 0),
                outs)
            recv_next = lax.ppermute(y, axis, fwd_perm)
            return (recv_next, outs), None

        (_, outs), _ = lax.scan(tick, (buf0, out0), jnp.arange(ticks))
        # broadcast the last stage's outputs (other ranks hold zeros)
        outs = lax.psum(outs, axis)
        return outs[None]

    specs_p = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(specs_p, P()), out_specs=P(axis),
    )(stage_params, x)[0]


def pipelined_loss(stage_fn, loss_fn, stage_params, x, targets, *, mesh,
                   n_micro: int, axis: str = "pipe"):
    """Mean loss over microbatches through the pipeline (grad-able)."""
    outs = pipeline_apply(stage_fn, stage_params, x, mesh=mesh,
                          n_micro=n_micro, axis=axis)
    return loss_fn(outs, targets)
