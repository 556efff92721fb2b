"""Pallas TPU kernel: batched frequency queries against a summary.

The serving-side hot spot: for a batch of query ids, return the Space Saving
estimate triple (f̂, ε, monitored). Same dense-match formulation as
ss_match, but the contraction runs over the *counter* axis, so the grid
iterates (c/BC, k/BK) with the k-axis minor and the query-tile outputs
accumulate across consecutive steps.

    f̂[q]  = Σ_i [s_items[i] == queries[q]] · s_counts[i]
    ε[q]  = Σ_i [s_items[i] == queries[q]] · s_errors[i]
    mon[q] = ∃i [s_items[i] == queries[q]]

The two weighted reductions are int32 select+sum on the VPU, as in
ss_combine.py, exact at any count. An f32 MXU contraction here was not:
on a TPU v5e it returned counts off by up to 2.8e4 against the jnp
reference for counts below 2^24 (the f32 dot runs in reduced-precision
passes), so served point reads disagreed with the summary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

EMPTY = -1


def _query_kernel(q_ref, s_ref, c_ref, e_ref, f_ref, eps_ref, mon_ref):
    i = pl.program_id(1)  # counter-tile index (minor)

    q = q_ref[...]        # (1, BQ) int32
    s = s_ref[...]        # (BK, 1) int32
    cnt = c_ref[...]      # (BK, 1) int32
    err = e_ref[...]      # (BK, 1) int32

    eq = (s == q) & (s != EMPTY)                       # (BK, BQ)
    zero = jnp.zeros((), jnp.int32)
    f_part = jnp.where(eq, cnt, zero).sum(axis=0, keepdims=True)  # (1, BQ)
    e_part = jnp.where(eq, err, zero).sum(axis=0, keepdims=True)
    m_part = eq.any(axis=0, keepdims=True).astype(jnp.int32)

    @pl.when(i == 0)
    def _init():
        f_ref[...] = jnp.zeros_like(f_ref)
        eps_ref[...] = jnp.zeros_like(eps_ref)
        mon_ref[...] = jnp.zeros_like(mon_ref)

    f_ref[...] += f_part
    eps_ref[...] += e_part
    mon_ref[...] = jnp.maximum(mon_ref[...], m_part)


@functools.partial(jax.jit, static_argnames=("block_k", "block_q", "interpret"))
def query_pallas(s_items, s_counts, s_errors, queries, *, block_k: int = 512,
                 block_q: int = 512, interpret: bool = False):
    k, = s_items.shape
    q, = queries.shape
    assert k % block_k == 0 and q % block_q == 0, (k, q, block_k, block_q)
    nq, nk = q // block_q, k // block_k

    f_hat, eps, mon = pl.pallas_call(
        _query_kernel,
        grid=(nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q), lambda j, i: (0, j)),
            pl.BlockSpec((block_k, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_k, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_k, 1), lambda j, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q), lambda j, i: (0, j)),
            pl.BlockSpec((1, block_q), lambda j, i: (0, j)),
            pl.BlockSpec((1, block_q), lambda j, i: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, q), jnp.int32),
            jax.ShapeDtypeStruct((1, q), jnp.int32),
            jax.ShapeDtypeStruct((1, q), jnp.int32),
        ],
        interpret=interpret,
    )(queries.reshape(1, q), s_items.reshape(k, 1),
      s_counts.astype(jnp.int32).reshape(k, 1),
      s_errors.astype(jnp.int32).reshape(k, 1))

    return f_hat.reshape(q), eps.reshape(q), mon.reshape(q).astype(bool)
