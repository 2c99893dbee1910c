"""Device mesh construction and sharding helpers.

The reference's multi-device story is host-orchestrated range splitting
(Cores.cs:544-613); its cluster tier adds a second, coarser host tier
(ClusterAccelerator.cs).  The TPU-native equivalents are a
``jax.sharding.Mesh`` over the chips of a slice (ICI) and — for multi-host —
the same mesh spanning processes over DCN (SURVEY.md §2.3 "parallelism
strategies" table).  This module owns the standard axis names used across
the framework:

- ``dp``   data parallel (batch)
- ``fsdp`` fully-sharded data parallel (batch + parameter shards)
- ``pp``   pipeline parallel (layer stages — pipeline/ builds on this)
- ``tp``   tensor parallel (matmul columns/rows over ICI)
- ``sp``   sequence/context parallel (ring attention / Ulysses)
- ``ep``   expert parallel (MoE experts)
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "AXIS_NAMES",
    "make_mesh",
    "auto_mesh",
    "named_sharding",
    "shard_batch",
    "replicated",
    "constrain",
    "shard_map",
    "set_mesh",
]

AXIS_NAMES = ("dp", "fsdp", "pp", "tp", "sp", "ep")


shard_map = jax.shard_map
set_mesh = jax.set_mesh


def make_mesh(
    devices: Sequence[jax.Device] | None = None,
    *,
    dp: int = 1,
    fsdp: int = 1,
    pp: int = 1,
    tp: int = 1,
    sp: int = 1,
    ep: int = 1,
) -> Mesh:
    """Build a mesh with the framework's canonical axis order.

    The axis sizes must multiply to the device count.  Axes of size 1 are
    kept in the mesh (harmless for XLA; keeps PartitionSpecs uniform).
    """
    if devices is None:
        devices = jax.devices()
    sizes = {"dp": dp, "fsdp": fsdp, "pp": pp, "tp": tp, "sp": sp, "ep": ep}
    total = math.prod(sizes.values())
    if total != len(devices):
        raise ValueError(
            f"mesh axes {sizes} multiply to {total} but {len(devices)} devices given"
        )
    arr = np.asarray(devices, dtype=object).reshape(tuple(sizes[a] for a in AXIS_NAMES))
    return Mesh(arr, AXIS_NAMES)


def auto_mesh(
    devices: Sequence[jax.Device] | None = None,
    *,
    fsdp: int = 1,
    pp: int = 1,
    tp: int = 1,
    sp: int = 1,
    ep: int = 1,
) -> Mesh:
    """Like :func:`make_mesh` but ``dp`` absorbs whatever device count the
    fixed axes leave over."""
    if devices is None:
        devices = jax.devices()
    fixed = fsdp * pp * tp * sp * ep
    if len(devices) % fixed != 0:
        raise ValueError(
            f"device count {len(devices)} not divisible by fixed axes product {fixed}"
        )
    return make_mesh(devices, dp=len(devices) // fixed, fsdp=fsdp, pp=pp, tp=tp, sp=sp, ep=ep)


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    """``named_sharding(mesh, 'dp', None, 'tp')`` →  NamedSharding over
    PartitionSpec('dp', None, 'tp')."""
    return NamedSharding(mesh, PartitionSpec(*spec))


def shard_batch(mesh: Mesh, batch, axis: str | tuple = ("dp", "fsdp")):
    """Place a host batch (pytree of arrays) with its leading dim sharded
    over the data axes."""
    def put(x):
        spec = PartitionSpec(axis, *([None] * (np.ndim(x) - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(put, batch)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, PartitionSpec())


def constrain(x, mesh: Mesh, *spec):
    """``with_sharding_constraint`` sugar usable inside jit."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, PartitionSpec(*spec)))
