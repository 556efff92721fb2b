"""Pallas TPU kernel: Space Saving match-count (the inner loop of the merge).

Replaces the paper's hash-table membership probe with a dense match matrix
tiled through VMEM:

    add_w[i]   = Σ_j [s_items[i] == h_items[j]] · h_weights[j]
    matched[j] = ∃i  [s_items[i] == h_items[j]]

For a (BK × BC) tile the kernel builds the equality mask with a VPU
broadcast-compare and reduces the weighted mask with an f32 dot so the MXU
does the contraction (weights are chunk counts ≤ 2^24, exact in f32).

Grid: (k/BK, c/BC) with the c-axis minor, so the ``add_w`` output block for
row-tile i is revisited on *consecutive* grid steps (required on TPU for
accumulating outputs). ``matched`` partials are written once per tile into a
(SUBLANES·k/BK, c) scratch-out and OR-reduced by the caller — this avoids a
second, conflicting revisit order in the same kernel.

Layout: all operands are kept 2-D ((k,1) and (1,c)) — Mosaic wants ≥2-D
tiles, and the (8,128)-lane VREG layout then maps naturally. Mosaic also
wants the last two dims of every block divisible by (8, 128) or equal to the
array's, so each tile's matched partial is written as a full (SUBLANES, BC)
sublane group (the row broadcast) rather than one (1, BC) row of an
(k/BK, c) array — the latter only lowers when k/BK == 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

EMPTY = -1
SUBLANES = 8      # rows of one int32 vreg tile: the matched-partials block


def _match_kernel(s_ref, h_ref, w_ref, add_ref, matched_ref):
    j = pl.program_id(1)

    s = s_ref[...]           # (BK, 1) int32
    h = h_ref[...]           # (1, BC) int32
    w = w_ref[...]           # (1, BC) int32

    eq = (s == h) & (s != EMPTY) & (h != EMPTY)          # (BK, BC) bool, VPU
    # weighted row-reduction on the MXU: eq_f32 @ w_f32^T  -> (BK, 1)
    partial = jax.lax.dot_general(
        eq.astype(jnp.float32), w.astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():
        add_ref[...] = jnp.zeros_like(add_ref)

    add_ref[...] += partial.astype(add_ref.dtype)
    # one (SUBLANES, BC) write per (i, j) tile; caller ORs over the rows.
    matched_ref[...] = jnp.broadcast_to(
        eq.any(axis=0, keepdims=True).astype(jnp.int32), matched_ref.shape)


@functools.partial(jax.jit, static_argnames=("block_k", "block_c", "interpret"))
def match_weights_pallas(s_items: jax.Array, h_items: jax.Array,
                         h_weights: jax.Array, *, block_k: int = 512,
                         block_c: int = 512, interpret: bool = False):
    """Tiled match-count. Shapes: s_items (k,), h_items/h_weights (c,).

    k and c must be multiples of the block sizes (ops.py pads). Returns
    (add_w (k,) int32, matched (c,) bool).
    """
    k, = s_items.shape
    c, = h_items.shape
    assert k % block_k == 0 and c % block_c == 0, (k, c, block_k, block_c)
    nk, nc = k // block_k, c // block_c

    s2 = s_items.reshape(k, 1)
    h2 = h_items.reshape(1, c)
    w2 = h_weights.astype(jnp.int32).reshape(1, c)

    add_w, matched_part = pl.pallas_call(
        _match_kernel,
        grid=(nk, nc),
        in_specs=[
            pl.BlockSpec((block_k, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_k, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((SUBLANES, block_c), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, 1), jnp.int32),
            jax.ShapeDtypeStruct((SUBLANES * nk, c), jnp.int32),
        ],
        interpret=interpret,
    )(s2, h2, w2)

    return add_w.reshape(k), matched_part.any(axis=0)
