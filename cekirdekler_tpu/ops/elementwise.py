"""Blockwise fused elementwise ops via Pallas.

``map_blocks`` turns any jnp elementwise function into a tiled Pallas
kernel: inputs are cut into (rows, 128) VMEM blocks on a 1-D grid and the
function is applied per block — one HBM read + one write per array
regardless of how many ops the function fuses (the HBM-bandwidth play of
SURVEY.md §"Design for tpu hardware").  XLA fuses most elementwise chains
by itself; this is the explicit path for chains XLA splits (e.g. around
custom dtypes) and the building block user Pallas kernels plug into the
framework with (kernel/registry.PythonKernel wraps ops like these).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import call_by_platform

__all__ = ["map_blocks", "saxpy"]

_LANES = 128


def map_blocks(
    fn: Callable,
    *arrays,
    block_rows: int = 256,
    interpret: bool | None = None,
):
    """Apply elementwise ``fn(*blocks) -> block`` over 1-D arrays of equal
    length (multiple of 128).  ``interpret=None`` lowers per dispatch
    platform when called under ``jit`` (as :func:`saxpy` does); called
    eagerly it follows the default backend (ops/platform.py)."""
    n = arrays[0].shape[0]
    if any(a.shape != (n,) for a in arrays):
        raise ValueError("map_blocks needs equal-length 1-D arrays")
    if n % _LANES != 0:
        raise ValueError(f"length ({n}) must be a multiple of {_LANES}")
    rows_total = n // _LANES
    rows = min(block_rows, rows_total)
    while rows_total % rows != 0:
        rows //= 2
    rows = max(rows, 1)

    def kernel(*refs):
        out_ref = refs[-1]
        out_ref[:] = fn(*(r[:] for r in refs[:-1]))

    def make_call(interp: bool):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(
                (rows_total, _LANES), arrays[0].dtype),
            grid=(rows_total // rows,),
            in_specs=[pl.BlockSpec((rows, _LANES), lambda i: (i, 0))
                      for _ in arrays],
            out_specs=pl.BlockSpec((rows, _LANES), lambda i: (i, 0)),
            interpret=interp,
        )

    out = call_by_platform(
        interpret, make_call,
        *(a.reshape(rows_total, _LANES) for a in arrays))
    return out.reshape(n)


@functools.partial(jax.jit, static_argnames=("alpha", "interpret"))
def saxpy(alpha, x, y, interpret: bool | None = None):
    """y + alpha·x, fused in one pass (``alpha`` a python scalar — folded
    into the kernel; pallas_call rejects captured array constants)."""
    a = float(alpha)
    return map_blocks(lambda xb, yb: yb + a * xb, x, y, interpret=interpret)
