"""Named-axis collective wrappers — the framework's communication backend.

The reference's inter-device "communication" is host-mediated buffer copies
(SURVEY.md §5.8: no NCCL/MPI; device→device pipelines bounce through host
arrays, ClPipeline.cs:624-1580; the cluster tier frames bytes over TCP).
On TPU the equivalents are XLA collectives riding ICI within a slice and
DCN across hosts — these wrappers are what the rest of the framework
(pipelines, ring attention, cluster tier) calls so every collective choice
is auditable in one place.

All functions must run inside ``shard_map``/``pjit`` with the named axis
bound by the enclosing mesh.
"""

from __future__ import annotations

from jax import lax

__all__ = [
    "psum",
    "pmean",
    "pmax",
    "all_gather",
    "reduce_scatter",
    "ppermute_ring",
    "all_to_all",
    "axis_index",
    "axis_size",
    "ring_next",
    "ring_prev",
]


def psum(x, axis: str):
    return lax.psum(x, axis)


def pmean(x, axis: str):
    return lax.pmean(x, axis)


def pmax(x, axis: str):
    return lax.pmax(x, axis)


def all_gather(x, axis: str, *, gather_axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def reduce_scatter(x, axis: str, *, scatter_axis: int = 0, tiled: bool = True):
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=tiled)


def axis_index(axis: str):
    return lax.axis_index(axis)


def axis_size(axis: str):
    """Static size of a bound mesh axis — a python int inside shard_map
    (perm tables, capacity math, unrolled schedules need one)."""
    return lax.axis_size(axis)


def _ring_perm(n: int, shift: int):
    return [(i, (i + shift) % n) for i in range(n)]


def ppermute_ring(x, axis: str, shift: int = 1):
    """Rotate shards around the ring by ``shift`` positions (the ICI
    replacement for the reference pipeline's host-hop forwardResults,
    SURVEY.md §2.1 #8)."""
    n = axis_size(axis)
    return lax.ppermute(x, axis, perm=_ring_perm(n, shift))


def ring_next(x, axis: str):
    return ppermute_ring(x, axis, 1)


def ring_prev(x, axis: str):
    return ppermute_ring(x, axis, -1)


def all_to_all(x, axis: str, *, split_axis: int, concat_axis: int, tiled: bool = True):
    """Transpose shard ownership between two tensor dimensions — the Ulysses
    sequence↔head exchange."""
    return lax.all_to_all(x, axis, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled)
