"""Parallel Space Saving (paper's Algorithm 1) on JAX meshes — primitives.

Three reduction strategies over device meshes, mirroring the paper's study:

  * :func:`butterfly_combine` — log₂(p) rounds of ``lax.ppermute`` + COMBINE
    over ONE mesh axis; every rank ends with the global summary (the
    message-passing analogue of the paper's MPI user-defined reduction,
    upgraded from a rank-0 tree to an allreduce-style butterfly).
  * :func:`allgather_combine` — all_gather the summaries (possibly over
    several axes at once) then tree-combine locally: the *flat MPI* analogue;
    moves p·k entries to every rank.
  * :func:`hierarchical_combine` — butterfly over the intra-pod axis first,
    then over the cross-pod axis: the *hybrid MPI/OpenMP* analogue — one
    cross-pod round instead of log₂(p); this is the configuration the paper
    shows wins at 512 cores.

All three evaluate the SAME canonical COMBINE tree on rank 0 (adjacent
pairing, see ``reduce_summaries``), so any strategy over any power-of-two
topology produces the bitwise-identical global summary.

This module holds the *primitives*; the consumer-facing entry points
(:func:`parallel_spacesaving`, :func:`frequent_items`) are owned by the
StreamRuntime subsystem (``repro.runtime``) and re-exported here for
backward compatibility — new code should drive ``repro.runtime`` directly.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.combine import combine, reduce_summaries
from repro.core.spacesaving import (Summary, init_summary, pad_stream, prune,
                                    spacesaving_chunked)


# ---------------------------------------------------------------------------
# Block decomposition (lines 1–2 of Algorithm 1)
# ---------------------------------------------------------------------------

def block_decompose(stream: jax.Array, workers: int,
                    multiple: int = 1) -> jax.Array:
    """Split a (N,) stream into (workers, per) EMPTY-padded blocks.

    ``per`` is ⌈N/workers⌉ rounded up to ``multiple`` (a chunk size), so
    every worker block feeds a chunked update path without further padding.
    This is THE canonical decomposition: the single-host engine's tenants,
    the StreamRuntime's shard×lane workers, and the paper's MPI ranks all
    index the same blocks, which is what makes their results comparable.
    """
    stream = jnp.asarray(stream)
    n = stream.shape[-1]
    per = -(-n // workers)
    per = -(-per // multiple) * multiple
    if per == 0:     # empty stream → (workers, 0); pad_stream can't pad to 0
        return stream.reshape(workers, 0)
    return pad_stream(stream, per * workers).reshape(workers, per)


# ---------------------------------------------------------------------------
# Mesh-axis reductions (use inside shard_map)
# ---------------------------------------------------------------------------

def butterfly_combine(s: Summary, axis_name: str, *, match_fn=None) -> Summary:
    """Recursive-doubling COMBINE allreduce over ``axis_name``.

    Round i exchanges summaries between ranks differing in bit i and merges;
    after log₂(p) rounds every rank holds the combined summary. Each round
    moves one k-counter summary (3·k ints) per rank — the same communication
    volume per round as the paper's MPI reduction, but contention-free.

    Recursive doubling needs a power-of-two axis (rank j's round-i partner
    is j XOR 2^i); on any other axis size this falls back to
    :func:`allgather_combine`, which is size-agnostic, instead of crashing.
    ``match_fn`` (``kernels.ops.combine_match`` contract) selects the merge
    kernel for every round.
    """
    p = lax.axis_size(axis_name)
    if p & (p - 1):
        return allgather_combine(s, (axis_name,), match_fn=match_fn)
    for i in range(int(math.log2(p))):
        stride = 1 << i
        perm = [(j, j ^ stride) for j in range(p)]
        other = jax.tree.map(lambda a: lax.ppermute(a, axis_name, perm), s)
        s = combine(s, other, match_fn=match_fn)
    return s


def allgather_combine(s: Summary, axis_names, *, match_fn=None) -> Summary:
    """Flat reduction: gather every rank's summary, tree-combine locally."""
    stacked = jax.tree.map(
        lambda a: lax.all_gather(a, axis_names, axis=0, tiled=False), s)
    # all_gather over multiple axes stacks one dim per axis; flatten to (P, k)
    def _flat(a):
        return a.reshape((-1,) + a.shape[-1:])
    stacked = Summary(*(_flat(x) for x in stacked))
    return reduce_summaries(stacked, match_fn=match_fn)


def _require_bound_axis(axis_name: str, role: str) -> int:
    """Resolve a mesh axis size, turning an unbound name into a ValueError.

    Inside ``shard_map`` an unknown axis name surfaces as an opaque
    NameError/KeyError from deep in the tracing machinery; callers that
    configure reductions from user input (RuntimeConfig, CLI flags) want
    the misconfiguration named instead.
    """
    try:
        return lax.axis_size(axis_name)
    except (NameError, KeyError):     # the tracers' unbound-axis errors
        raise ValueError(
            f"hierarchical_combine: {role} axis {axis_name!r} is not bound "
            f"in the current mesh. Pass an axis that exists in the "
            f"surrounding shard_map mesh, or outer_axis=None for a "
            f"single-pod reduction (equivalent to butterfly_combine over "
            f"the intra-pod axis).") from None


def hierarchical_combine(s: Summary, inner_axis: str,
                         outer_axis: str | None, *, match_fn=None) -> Summary:
    """Two-level reduction: intra-pod butterfly, then cross-pod butterfly.

    The paper's hybrid MPI/OpenMP finding, mesh-native: communication over
    the slow (cross-pod / DCN) axis drops from log₂(p_total) rounds to
    log₂(n_pods) rounds, with the fast ICI axis absorbing the rest.

    Both axes are validated up front: a mesh that lacks the cross-pod axis
    raises a ValueError naming the missing axis (instead of an opaque
    failure inside shard_map) — single-pod callers pass ``outer_axis=None``.
    """
    _require_bound_axis(inner_axis, "intra-pod")
    if outer_axis is not None:
        _require_bound_axis(outer_axis, "cross-pod")
    s = butterfly_combine(s, inner_axis, match_fn=match_fn)
    if outer_axis is not None:
        s = butterfly_combine(s, outer_axis, match_fn=match_fn)
    return s


# Strategy selection by name lives in the engine's reduction registry
# (repro.engine.reductions), which wraps the three combinators above.


# ---------------------------------------------------------------------------
# Algorithm 1 — single-program local pass (vmap over logical workers)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("p", "k", "chunk_size"))
def local_summaries(stream: jax.Array, *, p: int, k: int,
                    chunk_size: int = 1024) -> Summary:
    """Block decomposition + per-worker Space Saving (lines 2–5 of Alg. 1).

    The stream is padded and reshaped to (p, n/p); each logical worker runs
    the chunked TPU-native Space Saving over its block. Under pjit, sharding
    the leading dim over the ``data`` axis makes this the exact distributed
    program of the paper; on one device it is a vmap.
    """
    blocks = block_decompose(stream, p, chunk_size)
    init = init_summary(k)
    return jax.vmap(
        lambda b: spacesaving_chunked(init, b, chunk_size=chunk_size))(blocks)


def parallel_spacesaving(stream: jax.Array, *, k: int, p: int,
                         chunk_size: int = 1024,
                         kernel: str = "auto") -> Summary:
    """Algorithm 1: local Space Saving per block, then ParallelReduction.

    Thin wrapper over the StreamRuntime one-shot API
    (``repro.runtime.parallel_spacesaving``) — the runtime owns end-to-end
    ingestion now; this name stays importable from ``repro.core``. The
    merge kernel is selected by name (``kernel=``, resolved like
    ``EngineConfig.kernel``) — the former ``match_fn`` callable keyword is
    gone with the move to engine-managed dispatch.
    """
    from repro.runtime import parallel_spacesaving as _run
    return _run(stream, k=k, p=p, chunk_size=chunk_size, kernel=kernel)


def frequent_items(stream: jax.Array, *, k_majority: int,
                   counters: int | None = None, p: int = 1,
                   chunk_size: int = 1024):
    """End-to-end k-majority query: returns (items, f̂, candidate, guaranteed).

    ``counters`` defaults to the theory-minimal k (one counter per possible
    heavy hitter); more counters tighten the ε bounds. Delegates to the
    StreamRuntime one-shot API (``repro.runtime.frequent_items``).
    """
    from repro.runtime import frequent_items as _run
    return _run(stream, k_majority=k_majority, counters=counters, p=p,
                chunk_size=chunk_size)
