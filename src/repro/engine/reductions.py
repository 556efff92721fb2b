"""Reduction-strategy registry: how B tenant summaries become one.

Every strategy has the signature

    fn(stacked: Summary, axis_names: tuple[str, ...], *,
       match_fn=None, pair_fn=None) -> Summary

where ``stacked`` carries the tenant dim on axis 0 (each leaf is (B, k)) and
``axis_names`` are the mesh axes to reduce over *in addition to* the local
tenant dim, listed INNERMOST (fastest-varying / intra-pod) first — empty
outside shard_map, where every strategy degrades to the on-device tree
reduction (which pjit lowers to collectives when the tenant dim is
sharded). With that convention every strategy evaluates the same canonical
adjacent-pair COMBINE tree over the mesh-major rank order, which is what
keeps them bitwise-interchangeable (``_allgather`` gathers outermost-first
for the same reason). ``match_fn`` is the engine-resolved combine-match kernel
(``kernels.ops.combine_match`` contract) driving every COMBINE the strategy
performs; ``pair_fn`` (the ``reduce_summaries`` batched-pairwise contract)
replaces the local tree's vmapped COMBINE round wholesale — the engine
passes the fused megakernel's batched combine here when its flush resolved
fused. Strategies registered without either keyword still work — the
engine only passes what the callable accepts.

Built-ins mirror the paper's study (core/parallel.py):

  * ``local``        — log₂(B) rounds of vmapped COMBINE on-device.
  * ``butterfly``    — local reduce, then a recursive-doubling COMBINE
                       allreduce over the first mesh axis.
  * ``allgather``    — local reduce, then all_gather + tree-combine (the
                       flat-MPI analogue).
  * ``hierarchical`` — local reduce, then intra-pod butterfly followed by one
                       cross-pod butterfly (the hybrid MPI/OpenMP winner).

``register_reduction`` lets future PRs (sharded tenants, async ingest) plug
in strategies without touching engine code.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax

from repro.core.combine import reduce_summaries
from repro.core.parallel import (allgather_combine, butterfly_combine,
                                 hierarchical_combine)
from repro.core.spacesaving import Summary

Reduction = Callable[[Summary, Tuple[str, ...]], Summary]

_REGISTRY: Dict[str, Reduction] = {}


def register_reduction(name: str, fn: Reduction, *,
                       overwrite: bool = False) -> None:
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"reduction {name!r} already registered")
    _REGISTRY[name] = fn


def get_reduction(name: str) -> Reduction:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown reduction {name!r}; have "
                       f"{sorted(_REGISTRY)}") from None


def reduction_names():
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Built-ins
# ---------------------------------------------------------------------------

def _lanes(stacked: Summary, match_fn, pair_fn) -> Summary:
    """The on-device tree over the local tenant dim (``sketch.lane_reduce``
    in a profile's per-op names)."""
    with jax.named_scope("sketch.lane_reduce"):
        return reduce_summaries(stacked, match_fn=match_fn, pair_fn=pair_fn)


def _local(stacked: Summary, axis_names, *, match_fn=None,
           pair_fn=None) -> Summary:
    return _lanes(stacked, match_fn, pair_fn)


def _butterfly(stacked: Summary, axis_names, *, match_fn=None,
               pair_fn=None) -> Summary:
    s = _lanes(stacked, match_fn, pair_fn)
    with jax.named_scope("sketch.mesh_reduce"):
        for ax in axis_names:
            s = butterfly_combine(s, ax, match_fn=match_fn)
    return s


def _allgather(stacked: Summary, axis_names, *, match_fn=None,
               pair_fn=None) -> Summary:
    s = _lanes(stacked, match_fn, pair_fn)
    if axis_names:
        # all_gather stacks one dim per axis in the order given; reversing
        # the innermost-first convention gathers outermost-first, i.e. the
        # mesh-major global rank order the canonical COMBINE tree expects
        with jax.named_scope("sketch.mesh_reduce"):
            s = allgather_combine(s, tuple(reversed(axis_names)),
                                  match_fn=match_fn)
    return s


def _hierarchical(stacked: Summary, axis_names, *, match_fn=None,
                  pair_fn=None) -> Summary:
    s = _lanes(stacked, match_fn, pair_fn)
    if axis_names:
        inner = axis_names[0]
        outer = axis_names[1] if len(axis_names) > 1 else None
        with jax.named_scope("sketch.mesh_reduce"):
            s = hierarchical_combine(s, inner, outer, match_fn=match_fn)
    return s


register_reduction("local", _local)
register_reduction("butterfly", _butterfly)
register_reduction("allgather", _allgather)
register_reduction("hierarchical", _hierarchical)
