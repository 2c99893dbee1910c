"""Pallas TPU kernel for the mandelbrot workload.

The kernel-language path (workloads.MANDELBROT_SRC) lowers the escape loop
to a vectorized ``lax.while_loop`` over the whole launch chunk — every
iteration streams the full chunk's state. This Pallas version tiles the
flat pixel range into VMEM blocks on a 1-D grid: each program holds one
(rows, 128) block in registers/VMEM for its entire ``fori_loop``, so orbit
state never round-trips HBM and the VPU runs at full tilt.  The
benchmark's ``mandelbrot_balance_4chip`` cell runs it through ``compute()``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import call_by_platform

__all__ = ["mandelbrot_pallas", "MANDEL_LANES", "MANDEL_SUBLANES"]

MANDEL_LANES = 128      # TPU lane width
MANDEL_SUBLANES = 8     # f32 sublane tile


def _mandel_kernel(offset_ref, out_ref, *, x0, y0, dx, dy, width, max_iter, rows):
    """One grid step: compute escape counts for a (rows, 128) pixel block.

    Flat pixel index of element (r, c) in this block:
        offset + program_id * rows * 128 + r * 128 + c
    (``offset`` arrives in SMEM so the framework's chunked launcher can
    pass it at runtime without retracing.)
    """
    base = offset_ref[0, 0] + pl.program_id(0) * rows * MANDEL_LANES
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, MANDEL_LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, MANDEL_LANES), 1)
    idx = base + r * MANDEL_LANES + c
    px = idx % width
    py = idx // width
    cx = x0 + dx * px.astype(jnp.float32)
    cy = y0 + dy * py.astype(jnp.float32)

    # no bool/mask in the carry (Mosaic relayout limitation) and no wheres:
    # escaped orbits free-run to inf/nan, and since nan/inf compare False
    # against 4.0 the count freezes at the escape iteration regardless.
    # while_loop gives per-block early exit — a block whose pixels have all
    # escaped stops iterating (big win away from the set boundary).
    def cond(carry):
        i, live, _, _, _ = carry
        return jnp.logical_and(i < max_iter, live > 0.0)

    def body(carry):
        i, _, zx, zy, count = carry
        zx2 = zx * zx
        zy2 = zy * zy
        inside = (zx2 + zy2 < 4.0).astype(jnp.float32)
        count = count + inside
        t = zx2 - zy2 + cx
        zy = 2.0 * zx * zy + cy
        zx = t
        return i + 1, jnp.sum(inside), zx, zy, count

    # init the carry from computed values (cx·0), not jnp.zeros: constant
    # inits get a replicated Mosaic layout the loop body's computed carries
    # can't be relaid out to
    zeros = cx * 0.0
    _, _, _, _, count = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.float32(1.0), zeros, zeros, zeros)
    )
    out_ref[:] = count


@functools.partial(
    jax.jit,
    static_argnames=(
        "n", "x0", "y0", "dx", "dy", "width", "max_iter", "block_rows", "interpret",
    ),
)
def mandelbrot_pallas(
    n: int,
    x0: float,
    y0: float,
    dx: float,
    dy: float,
    width: int,
    max_iter: int,
    offset=0,
    block_rows: int = 512,  # device-timeline sweep on v5e: 512 > 256 > 128
    interpret: bool | None = None,
):
    """Escape counts (f32) for flat pixels [offset, offset+n).

    ``n`` must be a multiple of 128; blocks are (block_rows, 128);
    ``offset`` may be a traced scalar (no retrace per chunk).
    ``interpret=None`` lowers per dispatch platform (Mosaic on a TPU,
    the Pallas interpreter elsewhere — ops/platform.py).
    """
    if n % MANDEL_LANES != 0:
        raise ValueError(f"n ({n}) must be a multiple of {MANDEL_LANES}")
    rows_total = n // MANDEL_LANES
    rows = min(block_rows, rows_total)
    while rows_total % rows != 0:
        rows //= 2
    rows = max(rows, 1)
    # python-float scalars fold into the kernel trace (array constants are
    # rejected by pallas_call); f32 rounding of the coefficients matches the
    # kernel-language path
    kernel = functools.partial(
        _mandel_kernel,
        x0=float(np.float32(x0)), y0=float(np.float32(y0)),
        dx=float(np.float32(dx)), dy=float(np.float32(dy)),
        width=width, max_iter=max_iter, rows=rows,
    )
    grid = rows_total // rows

    def make_call(interp: bool):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(
                (rows_total, MANDEL_LANES), jnp.float32),
            grid=(grid,),
            in_specs=[
                pl.BlockSpec(
                    (1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM
                )
            ],
            out_specs=pl.BlockSpec((rows, MANDEL_LANES), lambda i: (i, 0)),
            interpret=interp,
        )

    out = call_by_platform(
        interpret, make_call, jnp.asarray(offset, jnp.int32).reshape(1, 1))
    return out.reshape(n)
