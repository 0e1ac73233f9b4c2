"""Named-axis collective helpers used inside jitted steps.

The single replacement for the reference's five comm backends (SURVEY.md §2.4).
All of these lower to XLA collectives that ride ICI within a slice and DCN
across slices — there is no rendezvous, no parameter server, no block manager.
"""

from __future__ import annotations

from typing import Any, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

AxisName = Union[str, Sequence[str]]


def psum(tree: Any, axis: AxisName = "dp") -> Any:
    return lax.psum(tree, axis_name=axis)


def pmean(tree: Any, axis: AxisName = "dp") -> Any:
    return lax.pmean(tree, axis_name=axis)


def all_gather(x, axis: AxisName = "dp", *, axis_index_groups=None, tiled=True):
    return lax.all_gather(x, axis_name=axis, tiled=tiled,
                          axis_index_groups=axis_index_groups)


def reduce_scatter(x, axis: AxisName = "dp", *, scatter_dimension=0,
                   axis_index_groups=None):
    return lax.psum_scatter(x, axis_name=axis,
                            scatter_dimension=scatter_dimension, tiled=True,
                            axis_index_groups=axis_index_groups)


def ppermute_shift(x, axis: AxisName = "sp", shift: int = 1):
    """Ring shift along an axis — building block for ring attention."""
    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name=axis, perm=perm)


def axis_index(axis: AxisName = "dp"):
    return lax.axis_index(axis)


def axis_size(axis: AxisName = "dp"):
    return lax.axis_size(axis)


def axis_bound(axis: str) -> bool:
    """True when ``axis`` is bound in the ambient mapped context (shard_map
    / pmap). Probing costs nothing: the size query constant-folds, and an
    unbound name raises instead of emitting a collective."""
    try:
        axis_size(axis)
        return True
    except (NameError, KeyError, ValueError, TypeError):
        return False


def grad_allreduce_mean(grads: Any, axes: Sequence[str] = ("dp", "fsdp")) -> Any:
    """Mean-reduce gradients over the data axes — the one-liner that replaces
    BigDL's AllReduceParameter push/pull cycle (reference:
    zoo/.../keras/models/Topology.scala:1203-1206, docs/docs/wp-bigdl.md:140-160).

    Axis names absent from the ambient mesh are skipped, so the default
    ``("dp", "fsdp")`` works unchanged inside a single-axis
    ``Mesh(devices, ("dp",))`` shard_map (reducing over an unbound name
    used to raise). Calling with NO bound axis at all still raises —
    silently returning unreduced gradients would let replicas diverge."""
    bound = [ax for ax in axes if axis_bound(ax)]
    if axes and not bound:
        raise NameError(
            f"grad_allreduce_mean: none of the axes {tuple(axes)} are "
            "bound in the ambient mesh — call it inside shard_map/pmap "
            "over at least one of them")
    out = grads
    for ax in bound:
        out = lax.pmean(out, axis_name=ax)
    return out
