"""Pallas TPU kernel: combine-match — the inner loop of summary-vs-summary
COMBINE (and, with the errors channel disabled, of the histogram merge).

Same tiling story as ss_match.py, but the candidate side is a full summary,
so the kernel carries BOTH value channels plus the summary-side match flag:

    add_c[i]     = Σ_j [s_items[i] == c_items[j]] · c_counts[j]
    add_e[i]     = Σ_j [s_items[i] == c_items[j]] · c_errors[j]
    matched_s[i] = ∃j  [s_items[i] == c_items[j]]
    matched_c[j] = ∃i  [s_items[i] == c_items[j]]

Per (BK × BC) tile the equality mask is a VPU broadcast-compare and the two
weighted row-reductions are int32 select+sum on the VPU — NOT the f32 MXU
dot of ss_match: combine operands are *cumulative* stream counts, which can
exceed the 2^24 f32-exact window on long streams, so the contraction stays
in int32 (exact at any count).

Grid: (k/BK, c/BC) with the c-axis minor, so the three summary-side outputs
(add_c, add_e, matched_s) are revisited on consecutive grid steps and
accumulate in VMEM (init at j == 0). ``matched_c`` partials are written once
per tile into a (SUBLANES·k/BK, c) scratch-out and OR-reduced by the caller —
exactly the ss_match convention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ss_match import SUBLANES

EMPTY = -1


def _combine_kernel(s_ref, ci_ref, cc_ref, ce_ref,
                    addc_ref, adde_ref, ms_ref, mc_ref):
    j = pl.program_id(1)

    s = s_ref[...]            # (BK, 1) int32
    ci = ci_ref[...]          # (1, BC) int32
    cc = cc_ref[...]          # (1, BC) int32
    ce = ce_ref[...]          # (1, BC) int32

    eq = (s == ci) & (s != EMPTY) & (ci != EMPTY)        # (BK, BC) bool, VPU
    zero = jnp.zeros((), jnp.int32)
    part_c = jnp.where(eq, cc, zero).sum(axis=1, keepdims=True)   # (BK, 1)
    part_e = jnp.where(eq, ce, zero).sum(axis=1, keepdims=True)
    part_m = eq.any(axis=1, keepdims=True).astype(jnp.int32)

    @pl.when(j == 0)
    def _init():
        addc_ref[...] = jnp.zeros_like(addc_ref)
        adde_ref[...] = jnp.zeros_like(adde_ref)
        ms_ref[...] = jnp.zeros_like(ms_ref)

    addc_ref[...] += part_c
    adde_ref[...] += part_e
    ms_ref[...] = jnp.maximum(ms_ref[...], part_m)
    # one (SUBLANES, BC) write per (i, j) tile; caller ORs over the rows.
    mc_ref[...] = jnp.broadcast_to(
        eq.any(axis=0, keepdims=True).astype(jnp.int32), mc_ref.shape)


@functools.partial(jax.jit, static_argnames=("block_k", "block_c", "interpret"))
def combine_match_pallas(s_items: jax.Array, c_items: jax.Array,
                         c_counts: jax.Array, c_errors: jax.Array, *,
                         block_k: int = 512, block_c: int = 512,
                         interpret: bool = False):
    """Tiled combine-match. Shapes: s_items (k,), c_* (c,), block multiples
    (ops.py pads). Returns (add_c (k,) i32, add_e (k,) i32, matched_s (k,)
    bool, matched_c (c,) bool).
    """
    k, = s_items.shape
    c, = c_items.shape
    assert k % block_k == 0 and c % block_c == 0, (k, c, block_k, block_c)
    nk, nc = k // block_k, c // block_c

    s2 = s_items.reshape(k, 1)
    ci2 = c_items.reshape(1, c)
    cc2 = c_counts.astype(jnp.int32).reshape(1, c)
    ce2 = c_errors.astype(jnp.int32).reshape(1, c)

    add_c, add_e, ms, mc_part = pl.pallas_call(
        _combine_kernel,
        grid=(nk, nc),
        in_specs=[
            pl.BlockSpec((block_k, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_k, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_k, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_k, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((SUBLANES, block_c), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, 1), jnp.int32),
            jax.ShapeDtypeStruct((k, 1), jnp.int32),
            jax.ShapeDtypeStruct((k, 1), jnp.int32),
            jax.ShapeDtypeStruct((SUBLANES * nk, c), jnp.int32),
        ],
        interpret=interpret,
    )(s2, ci2, cc2, ce2)

    return (add_c.reshape(k), add_e.reshape(k), ms.reshape(k) > 0,
            mc_part.any(axis=0))
