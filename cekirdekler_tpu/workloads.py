"""Upstream's demonstrations as kernels with their host references:
mandelbrot, n-body, streaming vector add, the wave stencil.

The reference ships these as its demo set — ``Tester.nBody``
(Tester.cs:7682-7799, also the device-ranking micro-benchmark used by
``devicesWithHighestDirectNbodyPerformance``, ClObjectApi.cs:1222-1244),
``stream_C_equals_A_plus_B_1M_elements`` (Tester.cs:7806-7843), and a
mandelbrot demo distributed only as a Windows binary
(mandelbrot_bench_v4.rar).  Here they are kernel-language strings, host
reference implementations (the reference's ±0.01f nBody tolerance
pattern) and the self-checking ``run_*`` drivers over ``compute()``.
No metrology lives here: the benchmark is ``BENCHMARK.json`` +
``benchmark/run.py``, and its numbers are lines of ``PERF_LEDGER.jsonl``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from .arrays.clarray import ClArray
from .core.cruncher import NumberCruncher
from .hardware import Devices

__all__ = [
    "MANDELBROT_SRC",
    "NBODY_SRC",
    "STREAM_SRC",
    "WAVE_SRC",
    "mandelbrot_pallas_kernel",
    "mandelbrot_host",
    "nbody_host_step",
    "MandelbrotResult",
    "run_mandelbrot",
    "run_nbody",
    "run_stream",
]


# One pixel per work item; escape-iteration count written as float so a
# single dtype covers TPU (no int32 penalty) and matches the reference demo's
# colorable output.
MANDELBROT_SRC = """
__kernel void mandelbrot(__global float* out,
                         float x0, float y0, float dx, float dy,
                         int width, int maxIter) {
    int i = get_global_id(0);
    float cx = x0 + dx * (float)(i % width);
    float cy = y0 + dy * (float)(i / width);
    float zx = 0.0f;
    float zy = 0.0f;
    int it = 0;
    while (zx*zx + zy*zy < 4.0f && it < maxIter) {
        float t = zx*zx - zy*zy + cx;
        zy = 2.0f*zx*zy + cy;
        zx = t;
        it++;
    }
    out[i] = (float)it;
}
"""

# Direct O(n^2) gravity step (reference: Tester.nBody kernel shape,
# Tester.cs:7682-7799).  Positions are read whole on every chip; velocities
# are updated only for the chip's own range slice.
NBODY_SRC = """
__kernel void nBody(__global float* x, __global float* y, __global float* z,
                    __global float* vx, __global float* vy, __global float* vz,
                    int n, float dt) {
    int i = get_global_id(0);
    float ax = 0.0f;
    float ay = 0.0f;
    float az = 0.0f;
    float xi = x[i];
    float yi = y[i];
    float zi = z[i];
    for (int j = 0; j < n; j++) {
        float ddx = x[j] - xi;
        float ddy = y[j] - yi;
        float ddz = z[j] - zi;
        float r2 = ddx*ddx + ddy*ddy + ddz*ddz + 0.0001f;
        float inv = 1.0f / (r2 * sqrt(r2));
        ax += ddx * inv;
        ay += ddy * inv;
        az += ddz * inv;
    }
    vx[i] += ax * dt;
    vy[i] += ay * dt;
    vz[i] += az * dt;
}
"""

# Streaming c = a + b (reference: Tester.cs:7806-7843, PIPELINE_DRIVER,
# zero-copy inputs).
STREAM_SRC = """
__kernel void streamAdd(__global float* a, __global float* b, __global float* c) {
    int i = get_global_id(0);
    c[i] = a[i] + b[i];
}
"""

# 8-tap wave-equation stencil (reference: Kamera.cs waveEquation shape,
# Kamera.cs:233-268) — static shifts crossing rows and lanes; exercises
# the Pallas halo-block path.
WAVE_SRC = """
__kernel void wave(__global float* p, __global float* pold, __global float* pnew) {
    int i = get_global_id(0);
    float lap = p[i-1] + p[i+1] + p[i-128] + p[i+128] + p[i-129] + p[i+129]
              + p[i-127] + p[i+127] - 8.0f*p[i];
    pnew[i] = 2.0f*p[i] - pold[i] + 0.2f*lap;
}
"""


def mandelbrot_pallas_kernel(interpret: bool | None = None):
    """The mandelbrot workload as a raw-Pallas :class:`PythonKernel` —
    the hand-tiled hot path (ops/mandelbrot.py) plugged into the same
    compute()/balancer machinery as the C-subset kernel.

    ``interpret=None`` lowers per dispatch platform (ops/platform.py):
    in a TPU + host-CPU fleet the chip lane compiles under Mosaic and the
    host lane interprets, from this one kernel object.  True/False forces
    one lowering on every lane."""
    import jax.lax

    from .kernel.registry import kernel
    from .ops.mandelbrot import mandelbrot_pallas

    @kernel(name="mandelbrot", static_values=True)
    def mandelbrot(gid, out, x0=0.0, y0=0.0, dx=0.0, dy=0.0, width=0, maxIter=0):
        chunk = gid.shape[0]
        piece = mandelbrot_pallas(
            chunk, x0, y0, dx, dy, width, maxIter, offset=gid[0],
            interpret=interpret,
        )
        if out.shape[0] == chunk:
            # whole-buffer launch (single chip, no blobbing): the result IS
            # the buffer — skip the read-modify-write update pass (~16% of
            # the headline iteration on v5e)
            return piece
        return jax.lax.dynamic_update_slice(out, piece, (gid[0],))

    return mandelbrot


def mandelbrot_host(
    width: int, height: int, x0: float, y0: float, dx: float, dy: float, max_iter: int
) -> np.ndarray:
    """Host reference implementation (vectorized numpy) for self-checking."""
    # all arithmetic in f32, matching the kernel's single-precision orbit
    px = np.arange(width * height, dtype=np.int64)
    cx = np.float32(x0) + np.float32(dx) * (px % width).astype(np.float32)
    cy = np.float32(y0) + np.float32(dy) * (px // width).astype(np.float32)
    zx = np.zeros_like(cx)
    zy = np.zeros_like(cy)
    it = np.zeros(width * height, dtype=np.int32)
    active = np.ones(width * height, dtype=bool)
    for _ in range(max_iter):
        zx2 = zx * zx
        zy2 = zy * zy
        active = active & (zx2 + zy2 < 4.0)
        if not active.any():
            break
        t = zx2 - zy2 + cx
        zy = np.where(active, 2.0 * zx * zy + cy, zy)
        zx = np.where(active, t, zx)
        it = it + active.astype(np.int32)
    return it.astype(np.float32)


def nbody_host_step(x, y, z, vx, vy, vz, dt: float):
    """Host reference for one nBody velocity update (numpy O(n^2))."""
    xs = x.astype(np.float64)
    ys = y.astype(np.float64)
    zs = z.astype(np.float64)
    ddx = xs[None, :] - xs[:, None]
    ddy = ys[None, :] - ys[:, None]
    ddz = zs[None, :] - zs[:, None]
    r2 = ddx * ddx + ddy * ddy + ddz * ddz + 0.0001
    inv = 1.0 / (r2 * np.sqrt(r2))
    vx2 = vx + (ddx * inv).sum(axis=1).astype(np.float32) * dt
    vy2 = vy + (ddy * inv).sum(axis=1).astype(np.float32) * dt
    vz2 = vz + (ddz * inv).sum(axis=1).astype(np.float32) * dt
    return vx2, vy2, vz2


@dataclass
class MandelbrotResult:
    mpixels_per_sec: float
    per_iter_ms: list[float] = field(default_factory=list)
    ranges_per_iter: list[list[int]] = field(default_factory=list)
    convergence_iters: int | None = None
    image: np.ndarray | None = None


def run_mandelbrot(
    devices: Devices | None = None,
    width: int = 2048,
    height: int = 2048,
    max_iter: int = 256,
    iters: int = 12,
    warmup: int = 2,
    pipeline: bool = False,
    pipeline_blobs: int = 8,
    local_range: int = 256,
    keep_image: bool = False,
    cruncher: NumberCruncher | None = None,
    use_pallas: bool = False,
    readback: str = "every",
    sync_every: int = 1,
) -> MandelbrotResult:
    """Timed, load-balanced mandelbrot over all selected chips.

    ``use_pallas`` swaps the kernel-language program for the hand-tiled
    Pallas kernel (same name, same compute path).  ``readback="final"``
    runs in enqueue mode — the image stays in HBM, iterations sync to a
    device barrier every ``sync_every`` steps (one host sync per window,
    not per iteration), and one flush at the end writes the host array
    (the device-throughput view; "every" includes a full D2H per
    iteration).
    Returns Mpixels/sec over the timed iterations plus per-iteration wall
    times and the balancer's range trajectory (for the convergence metric
    in BASELINE.md).
    """
    from .hardware import chip_devices

    own = cruncher is None
    devs = devices or chip_devices()
    source = mandelbrot_pallas_kernel() if use_pallas else MANDELBROT_SRC
    cr = cruncher or NumberCruncher(devs, source)
    n = width * height
    out = ClArray(n, np.float32, name="mandel_out", read=False, write=True)
    vals = (-2.0, -1.25, 2.5 / width, 2.5 / height, width, max_iter)
    per_iter: list[float] = []
    ranges: list[list[int]] = []
    if readback == "final":
        cr.enqueue_mode = True
    try:
        for k in range(warmup + iters):
            t0 = time.perf_counter()
            out.compute(
                cr, 7001, "mandelbrot", n, local_range,
                pipeline=pipeline, pipeline_blobs=pipeline_blobs, values=vals,
            )
            last = k == warmup + iters - 1
            if readback == "final" and ((k + 1) % sync_every == 0 or last):
                cr.barrier()
            dt_ms = (time.perf_counter() - t0) * 1000.0
            ranges.append(cr.ranges_of(7001))
            if k >= warmup:
                per_iter.append(dt_ms)
            elif k == warmup - 1 and readback == "final":
                # fence: warmup dispatches must retire OUTSIDE the timed
                # window or their device time deflates the metric
                cr.barrier()
        mpix = (n * len(per_iter)) / (sum(per_iter) / 1000.0) / 1e6
        step = local_range * (pipeline_blobs if pipeline else 1)
        if readback == "final":
            cr.enqueue_mode = False  # flush: one readback for the image
        return MandelbrotResult(
            mpixels_per_sec=mpix,
            per_iter_ms=per_iter,
            ranges_per_iter=ranges,
            convergence_iters=_converged_at(ranges, step),
            image=out.host().reshape(height, width).copy() if keep_image else None,
        )
    finally:
        # never leave a caller-supplied cruncher stuck in enqueue mode
        # (deferred readbacks would silently stop updating host arrays)
        if cr.enqueue_mode:
            try:
                cr.enqueue_mode = False
            except Exception:
                pass
        if own:
            cr.dispose()


def _converged_at(ranges: list[list[int]], step: int) -> int | None:
    """First iteration index after which every later re-balance moves no
    share by more than ``step`` (BASELINE.md convergence metric)."""
    for k in range(1, len(ranges)):
        if all(
            max(abs(a - b) for a, b in zip(ranges[j], ranges[j - 1])) <= step
            for j in range(k, len(ranges))
        ):
            return k
    return None


def run_nbody(
    devices: Devices | None = None,
    n: int = 8192,
    iters: int = 10,
    dt: float = 0.0001,
    local_range: int = 256,
    check: bool = True,
    tolerance: float = 0.01,
    use_jnp: bool = False,
) -> dict:
    """Load-balanced n-body velocity updates; self-checks the first step
    against the host O(n^2) reference within ``tolerance`` (the reference's
    ±0.01f pattern, Tester.cs:7682-7799).

    ``use_jnp`` swaps the C-subset kernel for the fused-XLA fast path
    (ops/nbody.py) — same name, same compute()/balancer machinery, the
    per-j gather loop replaced by one pairwise tile program."""
    from .hardware import chip_devices

    rng = np.random.default_rng(42)
    pos = (rng.random((3, n), dtype=np.float32) - 0.5) * 2.0
    x = ClArray(pos[0].copy(), name="x", read_only=True)
    y = ClArray(pos[1].copy(), name="y", read_only=True)
    z = ClArray(pos[2].copy(), name="z", read_only=True)
    vel = [ClArray(n, np.float32, name=f"v{c}", partial_read=True) for c in "xyz"]
    expected = None
    if check:
        expected = nbody_host_step(
            pos[0], pos[1], pos[2],
            np.zeros(n, np.float32), np.zeros(n, np.float32), np.zeros(n, np.float32),
            dt,
        )
    if use_jnp:
        from .ops.nbody import nbody_jnp_kernel

        source = nbody_jnp_kernel()
    else:
        source = NBODY_SRC
    cr = NumberCruncher(devices or chip_devices(), source)
    group = x.next_param(y, z, *vel)
    times: list[float] = []
    try:
        for k in range(iters):
            t0 = time.perf_counter()
            group.compute(cr, 7002, "nBody", n, local_range, values=(n, dt))
            times.append((time.perf_counter() - t0) * 1000.0)
            if k == 0 and check and expected is not None:
                for got, want, label in zip(vel, expected, "xyz"):
                    err = float(np.abs(got.host() - want).max())
                    if err > tolerance:
                        raise AssertionError(
                            f"nBody v{label} mismatch: max err {err} > {tolerance}"
                        )
        pairs_per_sec = n * n * len(times[1:]) / (sum(times[1:]) / 1000.0 + 1e-12)
        return {
            "n": n,
            "per_iter_ms": times,
            "gpairs_per_sec": pairs_per_sec / 1e9,
            "checked": bool(check),
        }
    finally:
        cr.dispose()


def _nbody_rig(n: int, prefix: str):
    """The n-body array rig (seeded positions read whole, velocities
    partial) — ONE construction, so runs that are compared with each other
    (streamed against monolithic, fused against per-call) share the seed,
    the operand layout and the flags."""
    rng = np.random.default_rng(42)
    pos = (rng.random((3, n), dtype=np.float32) - 0.5) * 2.0
    xyz = [
        ClArray(pos[i].copy(), name=f"{prefix}{c}", read_only=True)
        for i, c in enumerate("xyz")
    ]
    vel = [
        ClArray(n, np.float32, name=f"{prefix}v{c}", partial_read=True)
        for c in "xyz"
    ]
    return pos, xyz, vel


def run_stream(
    devices: Devices | None = None,
    n: int = 1 << 20,
    reps: int = 10,
    blobs: int = 8,
    local_range: int = 256,
    fast: bool = True,
) -> dict:
    """Streaming c = a + b with the driver-pipeline analogue
    (reference: Tester.cs:7806-7843 — 1M floats, 8 blobs, 10 reps,
    zero-copy FastArr inputs)."""
    from .hardware import chip_devices

    a = ClArray(n, np.float32, name="a", fast=fast, partial_read=True, read_only=True, zero_copy=fast)
    b = ClArray(n, np.float32, name="b", fast=fast, partial_read=True, read_only=True, zero_copy=fast)
    c = ClArray(n, np.float32, name="c", fast=fast, write_only=True)
    a.host()[:] = np.arange(n, dtype=np.float32) % 97
    b.host()[:] = np.arange(n, dtype=np.float32) % 89
    cr = NumberCruncher(devices or chip_devices(), STREAM_SRC)
    group = a.next_param(b, c)
    times: list[float] = []
    try:
        for _ in range(reps):
            t0 = time.perf_counter()
            group.compute(cr, 7003, "streamAdd", n, local_range, pipeline=True, pipeline_blobs=blobs)
            times.append((time.perf_counter() - t0) * 1000.0)
        want = a.host() + b.host()
        if not np.allclose(c.host(), want):
            raise AssertionError("stream add mismatch")
        best = min(times)
        # 3 arrays × 4 bytes move per element per rep
        return {
            "n": n,
            "per_rep_ms": times,
            "gb_per_sec": (3 * 4 * n) / (best / 1000.0) / 1e9,
        }
    finally:
        cr.dispose()
        for arr in (a, b, c):
            arr.dispose()
