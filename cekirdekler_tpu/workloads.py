"""Benchmark/validation workloads: mandelbrot, n-body, streaming vector add.

The reference ships these as its demo/benchmark set — ``Tester.nBody``
(Tester.cs:7682-7799, also the device-ranking micro-benchmark used by
``devicesWithHighestDirectNbodyPerformance``, ClObjectApi.cs:1222-1244),
``stream_C_equals_A_plus_B_1M_elements`` (Tester.cs:7806-7843), and a
mandelbrot demo distributed only as a Windows binary
(mandelbrot_bench_v4.rar).  Here they are first-class workloads written in
the kernel language, with host reference implementations for self-checking
(the reference's ±0.01f nBody tolerance pattern) and timing helpers that
feed BASELINE.md's metrics: Mpixels/sec, load-balance convergence
iterations.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Sequence

import numpy as np

from .arrays.clarray import ClArray
from .core.cruncher import NumberCruncher
from .hardware import Devices

__all__ = [
    "MANDELBROT_SRC",
    "NBODY_SRC",
    "STREAM_SRC",
    "mandelbrot_host",
    "nbody_host_step",
    "MandelbrotResult",
    "run_mandelbrot",
    "run_nbody",
    "run_stream",
    "convergence_iterations",
    "WAVE_SRC",
    "lowering_faceoff",
    "marker_overhead",
    "dispatch_floor_sweep",
    "duplex_ceiling",
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

# Compute-heavy stream: per-element iteration loop so blob compute time is
# commensurate with blob transfer time — the regime where the pipeline
# engines' read/compute/write overlap is actually measurable (on a slow
# host link, plain streamAdd is ~99% transfer and overlap is unobservable).
# The accumulation is EXACT in f32 (quarter-integer partial sums well below
# 2^24), so the result has a closed form the caller can assert against —
# a decaying recurrence has f32 fixed points a float64 model cannot predict.
STREAM_HEAVY_SRC = """
__kernel void streamHeavy(__global float* a, __global float* b, __global float* c,
                          int iters) {
    int i = get_global_id(0);
    float acc = a[i];
    for (int k = 0; k < iters; k++) {
        acc = acc + b[i] * 0.25f;
    }
    c[i] = acc;
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


def nbody_e2e(
    devices: Devices | None = None,
    n: int = 8192,
    iters: int = 150,
    window: int = 50,
    dt: float = 0.0001,
    local_range: int = 256,
    tolerance: float = 0.01,
    attribution: bool = False,
    probe_iters: int | None = None,
    device_timeline_dir: str | None = None,
    fused: bool = True,
) -> dict:
    """The reference's flagship numeric loop END-TO-END (VERDICT r4 #7):
    n-body at reference scale (n=8k, 150 load-balanced iterations, ±0.01f
    host check — Tester.cs:7682-7799) through the full ``compute()``
    path: scheduler, balancer, uploads, ladder launches, readbacks.

    Departures from the reference loop, both TPU-idiomatic:

    - **enqueue windows** (``window`` computes per barrier) instead of a
      sync per iteration: a per-iteration sync serializes host dispatch
      behind device retirement; the barrier measures per-lane retirement
      and arms the sync-point rebalance — the production mode for
      repeated same-shape work.
    - on a single-chip host the range is balanced across **2 partition
      lanes** of the chip (the reference's CPU-fission analogue,
      ClDevice.cs:85-95): the balancer genuinely moves shares between
      lanes on real hardware rather than being vacuous on one device.

    Correctness is the reference's own pattern: the first step's
    velocities against the host O(n²) reference within ±``tolerance``
    (checked synchronously, before the timed window loop; velocities then
    keep accumulating — per-iteration work is identical).

    ``attribution=True`` (VERDICT r5 #3) records the timed loop through
    ``cekirdekler_tpu.trace`` and NAMES each factor of the e2e-vs-device
    throughput gap with a measurement in the result's ``attribution``
    key: **window fence** (barrier fence spans — the per-window sync
    wait), **ladder launch** (host-side kernel dispatch spans),
    **upload/download** (transfer spans), **scheduler dispatch** (the
    enqueue spans' residue over the phases inside them), the
    **unattributed host gap**, and **lane interference** (a short
    single-lane probe run after the timed loop: factor = multi-lane
    per-iteration time × lanes / single-lane per-iteration time — 1.0
    means the lanes split the work perfectly, 2.0 means two partition
    lanes of one chip fully serialized against each other).
    ``device_timeline_dir`` additionally runs a SHORT separate enqueue
    window after the timed loop under a device-attribution capture
    (trace/device.py): an Xprof trace with per-launch correlation
    marks, reconciled against that probe window's wall and reported as
    the attribution's ``kernel_profile`` block (per-kernel device wall,
    op counts, idle gaps, coverage fraction, roofline row; a named
    ``{"absent": reason}`` on CPU-only rigs).  The headline wall itself
    is NEVER produced under the profiler — profiling perturbs it, and
    the gpairs key is regression-watched against unprofiled rounds.

    ``fused`` (default True — the production mode) lets the fused
    dispatch path collapse each window's repeated identical computes
    into batched single-ladder dispatches per lane (core/cores.py); the
    result's ``fused`` key reports windows/iterations/disengages, and
    with attribution on, a ``fused_dispatch`` factor accounts the ladder
    flush cost.  Note the factor semantics shift under fusion: iteration
    work dispatches in batches, so the barrier fence (``window_fence``)
    absorbs device-drain wait the per-iteration path hid inside its
    dispatch stream — read ``window_fence + ladder_launch +
    scheduler_dispatch`` together against wall, not fence alone.
    ``fused=False`` restores per-iteration dispatch exactly (the two
    paths are bit-identical; tests/test_fused.py pins it)."""
    from .hardware import chip_devices

    devs = devices if devices is not None else chip_devices()
    lanes = len(devs)
    probe_devs = devs.subset(1)  # un-partitioned: the 1-lane probe rig
    single_chip_partitions = lanes == 1
    if single_chip_partitions:
        devs = devs[0].as_partitions(2)
        lanes = 2
    pos, (x, y, z), vel = _nbody_rig(n, "e")
    expected = nbody_host_step(
        pos[0], pos[1], pos[2],
        np.zeros(n, np.float32), np.zeros(n, np.float32),
        np.zeros(n, np.float32), dt,
    )
    cid = 7010
    cr = NumberCruncher(devs, NBODY_SRC)
    cr.fused_dispatch = fused
    group = x.next_param(y, z, *vel)
    try:
        # synchronous first step: the ±0.01 host check
        group.compute(cr, cid, "nBody", n, local_range, values=(n, dt))
        max_err = max(
            float(np.abs(got.host() - want).max())
            for got, want in zip(vel, expected)
        )
        if max_err > tolerance:
            raise AssertionError(
                f"nBody e2e mismatch: max err {max_err} > {tolerance}"
            )
        # warm the fused ladder executable OUTSIDE the timed loop: XLA
        # compiles it at its first dispatch, and a compile inside the
        # window would charge seconds to ladder_launch/wall that no
        # steady-state run pays (the per-call ladder was warmed by the
        # sync step above).  Three extra untimed iterations — the window
        # engages on the first consecutive repeat, so call 3 is the
        # first DEFERRED one and the barrier's flush is what compiles
        # the ladder; physically identical work, velocities simply keep
        # accumulating.
        if fused:
            cr.enqueue_mode = True
            for _ in range(3):
                group.compute(cr, cid, "nBody", n, local_range, values=(n, dt))
            cr.barrier()
        # stats snapshot so the artifact counts the TIMED loop only —
        # including disengages: a warm-phase disengage must not read as
        # a fall-back inside the measured run
        fstats0 = {
            k: cr.cores.fused_stats[k]
            for k in ("windows", "fused_iters", "deferred_iters")
        }
        fstats0["disengaged"] = dict(cr.cores.fused_stats["disengaged"])
        # timed: the 150-iteration balanced loop in enqueue windows
        from .trace.spans import TRACER

        was_tracing = TRACER.enabled
        if attribution and not was_tracing:
            TRACER.enable(clear=True)
        traj: list[list[int]] = []
        cr.enqueue_mode = True
        t0 = time.perf_counter()
        wall = 0.0
        t_end = t0
        try:
            for k in range(iters):
                group.compute(cr, cid, "nBody", n, local_range, values=(n, dt))
                traj.append(cr.ranges_of(cid))
                if (k + 1) % window == 0:
                    cr.barrier()
            cr.enqueue_mode = False  # flush
            # wall closes inside the try: the finally's tracer disable
            # (and any exception bookkeeping) must not inflate the
            # headline.  The profiler never runs here — the device
            # capture lives in _nbody_device_profile's separate probe
            # window so Xprof cannot perturb the watched gpairs number.
            wall = time.perf_counter() - t0
            t_end = time.perf_counter()
        finally:
            # a failed loop must not leave the global tracer enabled,
            # taxing everything that runs after
            if attribution and not was_tracing:
                TRACER.disable()
        fstats = cr.cores.fused_stats
        out = {
            "n": n,
            "iters": iters,
            "lanes": lanes,
            "window": window,
            "gpairs_per_sec": round(n * n * iters / wall / 1e9, 3),
            "wall_ms": round(wall * 1e3, 1),
            "checked": True,
            "host_check_max_err": round(max_err, 5),
            "ranges_first": traj[0],
            "ranges_final": traj[-1],
            "convergence_iters": _converged_at(traj, local_range),
            # fused-dispatch observability: how much of the window rode
            # the single-ladder path, and every disengage by name — a
            # silent fall-back to per-iteration dispatch would otherwise
            # read as device slowness
            "fused": {
                "enabled": bool(fused),
                "windows": fstats["windows"] - fstats0["windows"],
                "fused_iters": fstats["fused_iters"] - fstats0["fused_iters"],
                "deferred_iters": (
                    fstats["deferred_iters"] - fstats0["deferred_iters"]
                ),
                "disengaged": {
                    k: v - fstats0["disengaged"].get(k, 0)
                    for k, v in fstats["disengaged"].items()
                    if v - fstats0["disengaged"].get(k, 0) > 0
                },
            },
        }
        if attribution:
            out["attribution"] = _nbody_attribution(
                TRACER.spans_between(t0, t_end), t0, t_end, wall, iters,
                lanes, probe_devs, n, dt, local_range, window,
                probe_iters,
                ring_wrapped=TRACER.total_recorded > TRACER.capacity,
                dropped_spans=TRACER.dropped_spans,
                single_chip_partitions=single_chip_partitions,
                fused=fused,
                lane_kinds=list(cr.cores.lane_kinds),
            )
            if device_timeline_dir:
                out["attribution"].update(_nbody_device_profile(
                    cr, group, cid, n, dt, local_range, window, iters,
                    device_timeline_dir,
                ))
        return out
    finally:
        if cr.enqueue_mode:
            try:
                cr.enqueue_mode = False  # flush replays deferred work
            except Exception:  # noqa: BLE001 - must not mask the root
                pass           # cause or skip the dispose below
        cr.dispose()


def _nbody_device_profile(
    cr, group, cid: int, n: int, dt: float, local_range: int,
    window: int, iters: int, trace_dir: str,
) -> dict:
    """The profiler-backed device/host split for nbody_e2e — measured
    in a SHORT separate enqueue window run AFTER the timed loop (the
    flash section's discipline): the headline gpairs number is never
    produced under the profiler, which perturbs it, so the watched
    ``nbody_e2e_enqueue_gpairs`` trajectory stays comparable with the
    unprofiled rounds.  Returns the keys merged into the attribution
    block; degrades to ``kernel_profile: {"absent": reason}`` when the
    capture holds no device events."""
    from .core.stream import plan_signature
    from .trace.device import STORE, DeviceCapture, roofline_row

    probe_iters = max(2, min(iters, window))
    cap = DeviceCapture(trace_dir)
    with cap:
        cr.enqueue_mode = True
        for _ in range(probe_iters):
            group.compute(cr, cid, "nBody", n, local_range, values=(n, dt))
        cr.barrier()
        cr.enqueue_mode = False
    rep = cap.report
    out: dict = {
        "device_events": rep.n_ops,
        "device_busy_ms": round(rep.device_busy_ms, 3),
        "device_busy_frac_of_wall": (
            round(rep.device_busy_ms / rep.wall_ms, 4)
            if rep.wall_ms > 0 and rep.absent is None else None
        ),
        # the per-kernel device report: device wall per kernel, op
        # counts, inter-op idle, per-lane overlap, coverage fraction —
        # or {"absent": <reason>} on CPU-only rigs
        "kernel_profile": (
            {"absent": rep.absent} if rep.absent is not None
            else {
                **rep.to_dict(),
                "profiled_iters": probe_iters,
                "note": ("profiled in a separate short window after "
                         "the timed loop — the headline wall ran "
                         "unprofiled"),
            }
        ),
    }
    if rep.absent is None:
        nb_prof = rep.kernel("nBody")
        if nb_prof is not None and nb_prof.device_ms > 0:
            # roofline/MFU row from the workload's analytic counts:
            # ~20 flops per pair interaction (3 sub, 6 FMA for r²,
            # rsqrt + scale, 6 FMA into v), and 9 array passes of
            # 4 B/element per iteration (x/y/z read, vx/vy/vz rw)
            rl = roofline_row(
                20.0 * float(n) * float(n) * probe_iters,
                9.0 * float(n) * 4 * probe_iters,
                nb_prof.device_ms,
                # the roof of the chips the lanes ran on (an unknown
                # kind raises — no assumed roof)
                device_kind=cr.cores.lane_kinds[0],
            )
            out["kernel_profile"]["roofline"] = rl
            # store key blocks = the per-lane range geometry (each
            # active lane's share determines its launch ladder) via the
            # ONE geometry-signature helper, per the store contract
            ranges = [r for r in cr.ranges_of(cid) if r > 0]
            STORE.put(
                "nBody", (n,), (plan_signature(ranges), local_range),
                {"device_ms": round(nb_prof.device_ms, 3),
                 "op_count": nb_prof.op_count,
                 "launches": nb_prof.launches,
                 "mfu": rl["mfu"], "bound": rl["bound"],
                 "probe_wall_ms": round(rep.wall_ms, 3),
                 "probe_iters": probe_iters, "window": window},
            )
    return out


def _nbody_rig(n: int, prefix: str):
    """The nbody_e2e array rig — ONE construction shared by the measured
    run and the lane-interference probe, so the two cannot silently
    desynchronize (same seed, same operand layout, same flags)."""
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


def _nbody_attribution(
    spans, t0, t_end, wall, iters, lanes, probe_devs, n, dt,
    local_range, window, probe_iters, ring_wrapped=False,
    dropped_spans=0, single_chip_partitions=False, fused=True,
    lane_kinds=None,
) -> dict:
    """Name each factor of the nbody_e2e gap with a measurement
    (VERDICT r5 #3).  Fractions are of the e2e wall; they need not sum
    to 1 — launches/uploads overlap device execution by design, and the
    lane-interference factor is a ratio, not a time share."""
    from .trace.attribution import union_ms, window_report

    rep = window_report(spans, t0, t_end, ring_wrapped=ring_wrapped,
                        dropped_spans=dropped_spans,
                        lane_kinds=lane_kinds)

    def _kind(kind):
        # the report's window-clipped totals — the same numbers its own
        # per_kind table shows, so the factor rows cannot disagree with it
        v = rep.per_kind.get(kind, {"ms": 0.0, "count": 0})
        return v["ms"], v["count"]

    def _tagged_fence(tag_prefix):
        # same clipping rule as the report: re-reduce the tag-filtered
        # subset through window_report itself so the window_fence factor
        # can never diverge from the per_kind fence convention
        sub = window_report(
            [s for s in spans
             if s.kind == "fence" and (s.tag or "").startswith(tag_prefix)],
            t0, t_end,
        ).per_kind.get("fence", {"ms": 0.0, "count": 0})
        return sub["ms"], sub["count"]

    wall_ms = wall * 1000.0
    fence_ms, n_barriers = _tagged_fence("barrier")
    launch_ms, n_launches = _kind("launch")
    upload_ms, n_uploads = _kind("upload")
    download_ms, n_downloads = _kind("download")
    up_chunk_ms, n_up_chunks = _kind("upload-chunk")
    down_chunk_ms, n_down_chunks = _kind("download-chunk")
    fused_ms, n_fused = _kind("fused")
    # scheduler residue: per enqueue span, its wall minus the UNION of
    # phase intervals inside it — raw per-kind sums double-count
    # concurrent lanes (2 lanes x 1 ms launch > a 1.5 ms enqueue wall)
    # and phases outside any enqueue span (the flush's downloads) are
    # not this residue's business
    phases = [
        s for s in spans
        if s.kind in (
            "launch", "upload", "download", "upload-chunk", "download-chunk",
        )
    ]
    sched_ms = 0.0
    for e in spans:
        if e.kind != "enqueue":
            continue
        inner = [
            (max(s.t0, e.t0), min(s.t1, e.t1))
            for s in phases
            if s.t1 > e.t0 and s.t0 < e.t1
        ]
        sched_ms += max(e.dur_ms - union_ms(inner), 0.0)

    def factor(ms, count=None):
        d = {"ms": round(ms, 3), "frac": round(ms / wall_ms, 4) if wall_ms else None}
        if count is not None:
            d["count"] = count
        return d

    out = {
        "wall_ms": round(wall_ms, 3),
        "factors": {
            "window_fence": factor(fence_ms, n_barriers),
            "ladder_launch": factor(launch_ms, n_launches),
            "upload": factor(upload_ms, n_uploads),
            "download_flush": factor(download_ms, n_downloads),
            # the STREAMED transfer path's chunks (zero on runs where the
            # monolithic path served every transfer): chunk time overlaps
            # compute by design, so a large ms with a small wall frac is
            # the pipeline WORKING, not a regression
            "upload_chunks": factor(up_chunk_ms, n_up_chunks),
            "download_chunks": factor(down_chunk_ms, n_down_chunks),
            "scheduler_dispatch": factor(sched_ms),
            "fused_dispatch": factor(fused_ms, n_fused),
            "host_gap": factor(rep.gap_ms),
        },
        "per_kind_ms": {
            k: round(v["ms"], 3) for k, v in rep.per_kind.items()
        },
        # heterogeneous fleets (ISSUE 20): where the window's lane-
        # tagged time went per DEVICE KIND — on a mixed TPU + host-CPU
        # Cores this is the split's per-silicon account; homogeneous
        # fleets see one row
        "per_lane_kind_ms": {
            k: {"ms": round(v["ms"], 3), "count": v["count"],
                "lanes": sorted(v["lanes"])}
            for k, v in rep.per_lane_kind.items()
        },
        "ring_wrapped": ring_wrapped,  # True = factors undercount
        "dropped_spans": dropped_spans,  # exactly how many spans wrapped away
        "note": (
            "fracs are of e2e wall and overlap device time by design; "
            "window_fence = barrier fences (sync cost per enqueue window), "
            "ladder_launch = host-side kernel dispatch, fused_dispatch = "
            "fused-window ladder flushes, host_gap = wall no span "
            "explains; lane_interference is a ratio (1.0 = lanes split "
            "the work perfectly, lanes_count = fully serialized)"
            + (
                "; FUSED path: iteration work dispatches in batches, so "
                "barrier fences absorb device-drain wait the "
                "per-iteration path hid inside its dispatch stream — "
                "judge window_fence+ladder_launch+scheduler_dispatch "
                "against wall, not the fence alone"
                if fused else ""
            )
        ),
    }
    # lane interference: short single-lane probe on the un-partitioned
    # device — perfect lane scaling predicts multi-lane per-iter =
    # single-lane per-iter / lanes
    p_iters = probe_iters if probe_iters is not None else max(
        window, min(iters // 3, 2 * window)
    )
    try:
        _, (x1, y1, z1), vel1 = _nbody_rig(n, "pe")
        cr1 = NumberCruncher(probe_devs, NBODY_SRC)
        cr1.fused_dispatch = fused  # probe rides the same dispatch mode
        g1 = x1.next_param(y1, z1, *vel1)
        try:
            g1.compute(cr1, 7011, "nBody", n, local_range, values=(n, dt))
            cr1.enqueue_mode = True
            if fused:
                # same untimed fused-ladder warm as the measured run (a
                # fresh cruncher means a fresh executable cache; 3 calls
                # = seed + engage + one deferred iteration to dispatch)
                for _ in range(3):
                    g1.compute(cr1, 7011, "nBody", n, local_range,
                               values=(n, dt))
                cr1.barrier()
            t1 = time.perf_counter()
            for k in range(p_iters):
                g1.compute(cr1, 7011, "nBody", n, local_range, values=(n, dt))
                if (k + 1) % window == 0:
                    cr1.barrier()
            cr1.enqueue_mode = False
            single_wall = time.perf_counter() - t1
        finally:
            if cr1.enqueue_mode:
                cr1.enqueue_mode = False
            cr1.dispose()
        per_iter_multi = wall_ms / iters
        per_iter_single = single_wall * 1000.0 / p_iters
        out["lane_interference"] = {
            "factor": round(per_iter_multi * lanes / max(per_iter_single, 1e-9), 3),
            "per_iter_ms_multi": round(per_iter_multi, 3),
            "per_iter_ms_single_lane": round(per_iter_single, 3),
            "lanes": lanes,
            "probe_iters": p_iters,
            "single_chip_partitions": single_chip_partitions,
        }
        if single_chip_partitions:
            # on the partition fallback both runs share ONE TensorCore,
            # so factor ≈ lanes is the EXPECTED floor (partition lanes
            # split a chip, they don't add one) — the factor then
            # measures partition-scheduling overhead ABOVE that floor,
            # not cross-chip interference; say so in the artifact before
            # someone chases a scheduler defect the metric can't see here
            out["lane_interference"]["note"] = (
                f"single-chip partition lanes: both runs share one core, "
                f"factor ≈ {lanes} is the expected floor; read the excess "
                f"over {lanes}, not the absolute value"
            )
    except Exception as e:  # noqa: BLE001 - probe failure must not kill e2e
        out["lane_interference"] = {"error": f"{type(e).__name__}: {e}"[:200]}
    return out


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


def measure_stream_overlap(
    devices: Devices | None = None,
    n: int = 1 << 22,
    blobs: int = 8,
    local_range: int = 256,
    pipeline_type: int | None = None,
    reps: int = 3,
    heavy_iters: int | str = 0,
    compute_factor: float = 1.0,
    duplex_probe: bool = False,
    streamed: bool = False,
) -> dict:
    """Measure the realized read/compute/write overlap fraction of the
    pipelined path on ONE chip (BASELINE.md metric 2; the engineered
    property behind the reference's 3× pipelining claim, Cores.cs:467).

    ``heavy_iters`` > 0 swaps the plain add for a per-element iteration
    kernel so blob compute is commensurate with blob transfer — on a slow
    host link plain streamAdd is ~99% transfer and r/c/w overlap is
    unobservable regardless of scheduling.  ``heavy_iters="auto"``
    CALIBRATES the iteration count to the link measured right now
    (compute ≈ read + write; capped at 150k to keep the exactness
    self-check's quarter-integer sums representable in f32) — a fixed
    count measures a different regime on every host link.  The chosen
    count is reported as ``heavy_iters`` in the result.

    Method (VERDICT r2 #3 — comparable phases, no clipping): ``reps``
    INTERLEAVED rounds, each measuring every phase once (asynchronous
    phases close with a device fence inside their timed window), and the
    per-phase MEDIAN across rounds is reported — these are host-clock
    times on a machine whose cores the scheduler, the transfers and the
    timer share, so separate multi-rep windows per phase let that noise
    masquerade as ±overlap.  ``sample_spread`` reports max per-phase
    (max-min)/median so the artifact shows how noisy the run was.

    ``compute_factor`` scales the ``"auto"`` calibration target: 1.0 is
    the balanced regime (compute ≈ read + write), 3.0 the compute-bound
    regime the reference's 3x claim describes (Cores.cs:467).

    ``duplex_probe=True`` interleaves pure H2D / D2H / duplex transfer
    samples INTO THE SAME rounds (VERDICT r4 #3: the ceiling and the
    achieved overlap must share a measurement window).  The ceiling
    is then computed PER REP from that rep's own complete sample by
    ``trace/ceiling.py`` (VERDICT r5 #4: the r5 cross-rep-median model
    read 1.15 — achieved above "ceiling" means the ruler was broken):
    each rep derives its duplex capacity, models
    ``p_model = max(c, r + w − dc·min(r, w)) + (r + w)/blobs``, and
    clamps the ceiling to the rep's own measured pipelined time (a run
    that happened is an existence proof the ceiling cannot exceed), so
    ``achieved_vs_ceiling`` — the MEDIAN of per-rep ratios, reported
    with ``achieved_vs_ceiling_spread`` — is structurally ≤ 1.0, and
    the BASELINE ≥0.9 target is judged against a real bound.

    ``streamed=True`` measures the STREAMED plain path instead of a
    pipeline engine: the "pipelined" phase becomes an ordinary
    ``compute()`` whose partition transfers ride the chunked
    double-buffered wavefront (``Cores._run_streamed`` — ladder-aligned
    chunks, autotuned count, depth-2 stream driver).  With
    ``duplex_probe`` on, the autotuner is seeded from a duplex sample
    taken BEFORE the timed rounds, and the result reports the chosen ``stream_chunks`` next to
    the overlap so the artifact shows WHAT the autotuner picked under
    the measured conditions.

    With median phase times r, c, w and pipelined total p::

        overlap = (r + c + w - p) / (r + c + w - max(r, c, w))

    1.0 = the pipelined total equals the slowest phase (perfect overlap);
    0.0 = fully serial.  The RAW ratio is returned — values < 0 mean
    pipeline overhead exceeded any overlap, values > 1 mean the phase
    decomposition was wrong; neither is hidden.  This is a host-window
    method: the phases are timed from the caller's side of the transfers.
    """
    from .core.cores import PIPELINE_EVENT
    from .hardware import chip_devices

    if pipeline_type is None:
        pipeline_type = PIPELINE_EVENT
    devs = (devices or chip_devices()).subset(1)
    kname = "streamHeavy" if heavy_iters else "streamAdd"
    auto_balance = heavy_iters == "auto"
    if auto_balance:
        heavy_iters = 1000  # placeholder until calibration below
    kvals = (heavy_iters,) if heavy_iters else ()
    cr = NumberCruncher(devs, STREAM_HEAVY_SRC if heavy_iters else STREAM_SRC)
    w = cr.cores.workers[0]
    a = ClArray(n, np.float32, name="ov_a", partial_read=True, read_only=True)
    b = ClArray(n, np.float32, name="ov_b", partial_read=True, read_only=True)
    c = ClArray(n, np.float32, name="ov_c", write_only=True)
    a.host()[:] = np.arange(n, dtype=np.float32) % 97
    b.host()[:] = np.arange(n, dtype=np.float32) % 89
    blob = n // blobs

    def fence():
        cr.barrier()

    def phase_read() -> None:
        for arr in (a, b):
            w.invalidate(arr)
        for k in range(blobs):
            for arr in (a, b):
                w.upload(arr, k * blob, blob, False)

    def phase_compute() -> None:
        # data already resident from the last read phase
        w.ensure_resident(c)
        for k in range(blobs):
            w.launch(
                cr.program, [kname], [a, b, c], kvals,
                k * blob, blob, local_range, n, local_range,
            )

    def phase_write() -> None:
        from .core.worker import Worker

        handles = [
            w.download_async(c, k * blob, blob, False) for k in range(blobs)
        ]
        for h in handles:
            Worker.finish_download(h)

    def phase_pipelined() -> None:
        for arr in (a, b, c):
            w.invalidate(arr)
        a.next_param(b, c).compute(
            cr, 7004, kname, n, local_range,
            pipeline=True, pipeline_blobs=blobs, pipeline_type=pipeline_type,
            values=kvals,
        )

    def phase_streamed() -> None:
        # the PLAIN path: partition transfers ride the chunked
        # double-buffered wavefront (Cores._run_streamed) — no pipeline
        # engine, no blob step change, same compile-once ladder
        for arr in (a, b, c):
            w.invalidate(arr)
        a.next_param(b, c).compute(
            cr, 7004, kname, n, local_range, values=kvals,
        )

    phase_pipe = phase_streamed if streamed else phase_pipelined

    def timed(fn, needs_fence: bool) -> float:
        t0 = time.perf_counter()
        fn()
        if needs_fence:
            fence()
        return max((time.perf_counter() - t0) * 1000.0, 1e-6)

    try:
        # warmup: compile + first-touch, and all four paths exercised once
        phase_read()
        phase_compute()
        fence()
        phase_write()
        phase_pipe()
        if auto_balance:
            # calibrate iters so compute ~= read + write ON THIS LINK —
            # a fixed iteration count tuned for one link speed measures
            # the transfer-bound regime on a slower link, and overlap of
            # a mismatched regime says nothing about the engine
            # min-of-2 like the compute probes: one host-noise spike on
            # a single read sample would otherwise floor/ceil the result
            t_r0 = max(
                min(timed(phase_read, True), timed(phase_read, True)), 1e-3)

            def t_compute_at(iters: int) -> float:
                t0 = time.perf_counter()
                w.ensure_resident(c)
                for k in range(blobs):
                    w.launch(
                        cr.program, [kname], [a, b, c], (iters,),
                        k * blob, blob, local_range, n, local_range,
                    )
                fence()
                return (time.perf_counter() - t0) * 1000.0

            c1 = min(t_compute_at(2000), t_compute_at(2000))
            c2 = min(t_compute_at(6000), t_compute_at(6000))
            if c2 - c1 <= 0:
                # a noise spike inverted the two samples: keep the
                # r3 default rather than calibrating into an extreme
                heavy_iters = 30000
            else:
                # compute-phase model: intercept + slope*iters — the
                # intercept (fixed dispatch cost per phase) matters on a
                # fast link where it rivals the transfer time
                slope = (c2 - c1) / 4000.0  # ms per iteration
                intercept = max(c1 - 2000.0 * slope, 0.0)
                # target: compute ~= compute_factor * (read + write),
                # read + write ~= 2*t_r0
                # cap 150k: the exactness self-check below needs the
                # quarter-integer accumulation to stay < 2^22
                # (150k iters x 0.25 x max(b)=88 ~= 3.3M), and beyond it
                # the regime is compute-bound anyway
                heavy_iters = int(min(
                    max(
                        (compute_factor * 2.0 * t_r0 - intercept) / slope,
                        1000,
                    ),
                    150_000,
                ))
            kvals = (heavy_iters,)
        # INTERLEAVED rounds: host-clock times on a shared machine wander,
        # so measuring each phase in its own multi-rep window lets that
        # masquerade as ±overlap; round-robin sampling keeps every phase's
        # samples seconds apart and the per-phase MEDIAN cancels it
        samples: dict[str, list[float]] = {
            "r": [], "c": [], "w": [], "p": [],
            "h2d": [], "d2h": [], "dup": [],
        }
        if duplex_probe:
            import jax
            import jax.numpy as jnp

            jdev = devs[0].jax_device
            dup_host = np.arange(n, dtype=np.float32)
            dup_base = jax.device_put(jnp.zeros(n, jnp.float32), jdev)
            jax.block_until_ready(dup_base)
            dup_k = [0]

            def _fresh_host():
                dup_k[0] += 1
                dup_host[0] = dup_k[0]
                return dup_host

            def _fresh_dev():
                dup_k[0] += 1
                y = dup_base + np.float32(dup_k[0])
                jax.block_until_ready(y)
                return y

            def probe_duplex(into: dict | None = None) -> None:
                """One H2D, one D2H, one duplex sample — fresh payloads
                (a device array caches its host copy after the first
                read-back), same 4n bytes as the phases.  ``into``
                redirects the samples (the autotuner's seeding probe must
                not enter the per-rep pairing)."""
                dst = samples if into is None else into
                h = _fresh_host()
                t0 = time.perf_counter()
                jax.block_until_ready(jax.device_put(h, jdev))
                dst["h2d"].append((time.perf_counter() - t0) * 1000.0)
                y = _fresh_dev()
                t0 = time.perf_counter()
                np.asarray(y)
                dst["d2h"].append((time.perf_counter() - t0) * 1000.0)
                y = _fresh_dev()
                h = _fresh_host()
                t0 = time.perf_counter()
                x = jax.device_put(h, jdev)  # async H2D
                np.asarray(y)                # D2H
                jax.block_until_ready(x)
                dst["dup"].append((time.perf_counter() - t0) * 1000.0)

            if streamed:
                # seed the transfer autotuner from a duplex sample taken
                # right before the timed rounds (per-MiB cost each
                # direction; the seeding sample stays out of the per-rep
                # ceiling pairing)
                scratch: dict = {"h2d": [], "d2h": [], "dup": []}
                probe_duplex(into=scratch)
                mib = (4.0 * n) / float(1 << 20)
                cr.cores.transfer_tuner.seed_link(
                    w.index, scratch["h2d"][0] / mib, scratch["d2h"][0] / mib
                )

        if streamed:
            # the warmup's measuring run observed the PRE-calibration
            # workload (with heavy_iters="auto" it ran the 1000-iter
            # placeholder): drop it, or the first chunked settle run
            # below would blame the calibration's extra compute on
            # per-chunk overhead, freeze the tuner at 1 chunk, and the
            # timed rounds would silently measure the monolithic path
            # while reporting transfer_path="streamed-ladder"
            cr.cores.transfer_tuner.on_repartition()
            # this deliberate drop is NOT a balancer re-partition: take
            # the baseline after it so the reported count stays "re-tunes
            # forced by re-partitions" (and keeps agreeing with
            # ck_stream_retune_total, which only the balancer path incs)
            retunes0 = cr.cores.transfer_tuner.retunes
            # untimed tuner-settle runs: the first streamed call is the
            # tuner's monolithic measuring run (at the CALIBRATED
            # workload), the next pays the chunked exploration that
            # teaches the lane's REAL per-chunk overhead (sub-ms on a
            # TPU lane, tens of ms on a CPU interpreter) — the timed
            # rounds then measure the SETTLED configuration, not the
            # learning transient
            phase_pipe()
            phase_pipe()
        for _ in range(reps):
            samples["r"].append(timed(phase_read, True))
            samples["c"].append(timed(phase_compute, True))
            samples["w"].append(timed(phase_write, False))
            samples["p"].append(timed(phase_pipe, False))
            if duplex_probe:
                probe_duplex()

        def med(key: str) -> float:
            vals = sorted(samples[key])
            return vals[len(vals) // 2]

        t_r, t_c, t_w, t_p = med("r"), med("c"), med("w"), med("p")
        serial = t_r + t_c + t_w
        ideal = serial - max(t_r, t_c, t_w)
        overlap = (serial - t_p) / ideal if ideal > 1e-9 else 0.0
        spread = max(
            (max(samples[k]) - min(samples[k])) / max(med(k), 1e-9)
            for k in ("r", "w", "p")
        )
        ceiling_keys: dict = {}
        if duplex_probe:
            # per-rep ceilings from each rep's OWN complete sample
            # (trace/ceiling.py: same-rep duplex capacity + fill/drain
            # edge + witness clamp), reduced to median ± spread — the
            # r5 cross-rep-median model could read >1; this cannot
            from .trace.ceiling import RepSample, ceiling_report

            reps_full = [
                RepSample(
                    r=samples["r"][i], c=samples["c"][i], w=samples["w"][i],
                    p=samples["p"][i], h2d=samples["h2d"][i],
                    d2h=samples["d2h"][i], dup=samples["dup"][i],
                )
                for i in range(len(samples["p"]))
                if i < len(samples["dup"])
            ]
            # the fill/drain edge term scales with the schedule's actual
            # chunk granularity: the engine's blob count, or the chunk
            # count the autotuner picked for the streamed path
            eff_blobs = blobs
            if streamed:
                eff_blobs = max(
                    cr.cores.last_stream_chunks.get(w.index, 1), 1
                )
            ceiling_keys = {
                "duplex_h2d_ms": round(med("h2d"), 3),
                "duplex_d2h_ms": round(med("d2h"), 3),
                "duplex_ms": round(med("dup"), 3),
                "compute_transfer_ratio": round(t_c / max(t_r + t_w, 1e-9), 2),
                **ceiling_report(reps_full, eff_blobs),
            }
        if heavy_iters:
            # acc = a + iters*(b/4), exact in f32 (quarter-integer sums
            # below 2^24) — the timing numbers are only publishable if the
            # pipelined path computed the right thing
            want = a.host() + heavy_iters * 0.25 * b.host()
            np.testing.assert_allclose(c.host(), want, rtol=1e-6)
        else:
            np.testing.assert_allclose(c.host(), a.host() + b.host())
        stream_keys: dict = {}
        if streamed:
            stream_keys = {
                "transfer_path": "streamed-ladder",
                "stream_chunks": cr.cores.last_stream_chunks.get(
                    w.index, 1
                ),
                "autotuner_retunes": (
                    cr.cores.transfer_tuner.retunes - retunes0
                ),
            }
        return {
            "t_read_ms": t_r,
            "t_compute_ms": t_c,
            "t_write_ms": t_w,
            "t_pipelined_ms": t_p,
            "t_serial_ms": serial,
            "overlap_fraction": overlap,  # RAW — see docstring
            "sample_spread": spread,  # >1 = host noise swamps the signal
            "n": n,
            "blobs": blobs,
            "reps": reps,
            "heavy_iters": int(heavy_iters) if heavy_iters else 0,
            **stream_keys,
            **ceiling_keys,
        }
    finally:
        cr.dispose()


def overlap_chunk_sweep(
    devices: Devices | None = None,
    ns: tuple[int, ...] = (1 << 20, 1 << 22),
    chunk_counts: tuple[int, ...] = (1, 2, 4, 8, 16, 32),
    local_range: int = 256,
    reps: int = 3,
    heavy_iters: int = 400,
) -> dict:
    """Chunk-count × array-size sweep of the STREAMED plain path
    (``tools/overlap_sweep.py``'s measurement): for each size, time the
    streamed compute with the chunk count PINNED at each candidate, then
    let the autotuner choose — by that point it has honest monolithic
    observations (the pinned c=1 rows) plus chunked refinements from the
    rest of the sweep, exactly the inputs it sees in production — and
    report its chosen point against the sweep optimum.

    Per size: ``rows`` (chunks → median wall ms), ``sweep_best_chunks``
    / ``sweep_best_ms`` (the measured argmin), ``autotuner_chunks`` /
    ``autotuner_ms`` (the choice and its measured wall), and
    ``choice_vs_optimum`` = autotuner wall / optimum wall (1.0 = the
    tuner found the measured optimum; the grid's discreteness and link
    drift make ~1.1 normal).  Walls are raw comparative medians — same
    rig, same rounds, so the ratio is the honest signal."""
    from .hardware import chip_devices

    devs = (devices or chip_devices()).subset(1)
    kname = "streamHeavy" if heavy_iters else "streamAdd"
    kvals = (heavy_iters,) if heavy_iters else ()
    bad = [n for n in ns if n < local_range or n % local_range]
    if bad:
        raise ValueError(
            f"sweep sizes {bad} are not multiples of local_range "
            f"{local_range} — compute() would reject them; pass --local"
        )
    # chunks=1 (the monolithic identity baseline) is always swept: it is
    # valid at any n, so the rows list can never end up empty when every
    # user-passed count exceeds n//local_range
    chunk_counts = tuple(sorted({1, *(int(c) for c in chunk_counts)}))
    sizes_out: list[dict] = []
    for n in ns:
        cr = NumberCruncher(
            devs, STREAM_HEAVY_SRC if heavy_iters else STREAM_SRC
        )
        w = cr.cores.workers[0]
        a = ClArray(n, np.float32, name="sw_a", partial_read=True,
                    read_only=True)
        b = ClArray(n, np.float32, name="sw_b", partial_read=True,
                    read_only=True)
        c = ClArray(n, np.float32, name="sw_c", write_only=True)
        a.host()[:] = np.arange(n, dtype=np.float32) % 97
        b.host()[:] = np.arange(n, dtype=np.float32) % 89

        def run_once() -> float:
            for arr in (a, b, c):
                w.invalidate(arr)
            t0 = time.perf_counter()
            a.next_param(b, c).compute(
                cr, 7104, kname, n, local_range, values=kvals
            )
            return (time.perf_counter() - t0) * 1000.0

        try:
            rows: list[dict] = []
            # chunks=1 is the monolithic path — valid at ANY n, so the
            # floor keeps a sub-local_range size from emptying the sweep
            max_chunks = max(1, n // local_range)
            for cc in chunk_counts:
                if cc > max_chunks:
                    continue
                cr.stream_chunks = cc  # 1 pins the monolithic path
                run_once()  # warm: ladder compile + tuner observation
                wall = float(np.median([run_once() for _ in range(reps)]))
                rows.append({"chunks": cc, "wall_ms": round(wall, 3)})
            best = min(rows, key=lambda r: r["wall_ms"])
            cr.stream_chunks = 0  # autotune from the sweep's observations
            run_once()  # the choice lands in last_stream_chunks
            auto_wall = float(np.median([run_once() for _ in range(reps)]))
            chosen = cr.cores.last_stream_chunks.get(w.index, 1)
            sizes_out.append({
                "n": n,
                "mib": round((3 * 4 * n) / float(1 << 20), 1),
                "rows": rows,
                "sweep_best_chunks": best["chunks"],
                "sweep_best_ms": best["wall_ms"],
                "autotuner_chunks": chosen,
                "autotuner_ms": round(auto_wall, 3),
                "choice_vs_optimum": round(
                    auto_wall / max(best["wall_ms"], 1e-9), 3
                ),
            })
        finally:
            cr.dispose()
            for arr in (a, b, c):
                arr.dispose()
    return {
        "note": (
            "streamed-path walls (ms, median of reps) per pinned chunk "
            "count; autotuner row = the count Cores.transfer_tuner "
            "chooses AFTER the sweep taught it this rig's link"
        ),
        "heavy_iters": heavy_iters,
        "local_range": local_range,
        "reps": reps,
        "sizes": sizes_out,
    }


def convergence_iterations(
    devices: Devices | None = None, max_iter: int = 192, width: int = 1024, height: int = 1024
) -> int | None:
    """Measure load-balance convergence on the mandelbrot workload
    (BASELINE.md: 'iterations until max share delta < step')."""
    res = run_mandelbrot(devices, width=width, height=height, max_iter=max_iter, iters=16, warmup=0)
    return res.convergence_iters


# ---------------------------------------------------------------------------
# lowering faceoff: the two kernel-language lowerings compared at device
# throughput
# ---------------------------------------------------------------------------

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


def lowering_faceoff(
    nbody_n: int = 8192,
    wave_n: int = 1 << 24,
    mandel_wh: int = 2048,
    reps: int = 16,
    wave_reps: int = 192,
    nbody_reps: int = 64,
) -> dict:
    """Device-throughput comparison of the XLA and Pallas lowerings on the
    three subset shapes: mandelbrot (elementwise + divergent loop), n-body
    (lane-uniform gather loop -> SMEM operand), wave stencil (static
    shifts -> halo blocks).

    Methodology: each measurement runs ``reps`` DEPENDENT steps INSIDE one
    jitted ``lax.fori_loop`` (each step's output feeds the next step's
    input, so XLA can neither dead-code-eliminate nor hoist a step, and
    the per-launch host dispatch cost is paid once, not per step), closed
    by ``block_until_ready``.  This reports DEVICE throughput of the
    lowering itself — the compute()-harness benches (run_mandelbrot /
    run_nbody) include scheduler + transfer + sync costs on top and
    answer a different question.  Needs the chip: the Pallas side is
    compiled under Mosaic (``interpret=False``).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from .kernel import codegen, lang
    from .kernel.pallas_backend import build_kernel_fn_pallas

    def chain(fn, arrs, make_vals, rotate, nreps):
        """Best-of-3 seconds per step: nreps dependent steps in ONE jitted
        fori_loop, one device fence.  Only valid when each step READS the
        previous step's output — a write-only chain would be
        dead-code-eliminated down to its last step."""

        @jax.jit
        def run(arrs):
            def step(j, cur):
                out = fn(0, cur, make_vals(j))
                return rotate(cur, out)

            return lax.fori_loop(0, nreps, step, tuple(arrs))

        cur = jax.block_until_ready(run(tuple(arrs)))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(run(tuple(cur)))
            best = min(best, (time.perf_counter() - t0) / nreps)
        return best

    def faceoff(kdef, arrs, make_vals, rotate, nreps):
        n = arrs[0].shape[0]
        xla_fn, _ = codegen.build_kernel_fn(kdef, n, 256, n)
        # force=True: measure the Pallas path even where the routing
        # policy (informed by THIS bench) prefers XLA — the faceoff is
        # the evidence the policy rests on
        pl_fn, _ = build_kernel_fn_pallas(kdef, n, 256, n, force=True)
        dt_x = chain(xla_fn, arrs, make_vals, rotate, nreps)
        dt_p = chain(pl_fn, arrs, make_vals, rotate, nreps)
        v0 = make_vals(0)
        ox = jax.jit(xla_fn)(0, tuple(arrs), v0)
        op = jax.jit(pl_fn)(0, tuple(arrs), v0)
        match = all(
            np.allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)
            for a, b in zip(ox, op)
        )
        return dt_x, dt_p, match

    rng = np.random.default_rng(42)
    out: dict = {"reps": reps, "wave_reps": wave_reps,
                 "nbody_reps": nbody_reps}

    # mandelbrot writes a fresh image each launch (out is write-only, so a
    # dependent in-jit chain is impossible — it would dead-code-eliminate);
    # instead: reps separate launches, dispatch cost paid per launch.  The
    # kernel time is several times that cost, so the ratio is mildly
    # compressed toward 1 — reported as-is.
    kdef = {k.name: k for k in lang.parse_kernels(MANDELBROT_SRC)}["mandelbrot"]
    N = mandel_wh * mandel_wh
    marrs = (jnp.zeros(N, jnp.float32),)
    mvals = (
        np.float32(-2.0), np.float32(-1.25),
        np.float32(2.5 / mandel_wh), np.float32(2.5 / mandel_wh),
        np.int32(mandel_wh), np.int32(256),
    )

    def mandel_time(fn):
        f = jax.jit(fn)
        jax.block_until_ready(f(0, marrs, mvals))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            o = None
            for _ in range(reps):
                o = f(0, marrs, mvals)
            jax.block_until_ready(o)
            best = min(best, (time.perf_counter() - t0) / reps)
        return best

    xla_fn, _ = codegen.build_kernel_fn(kdef, N, 256, N)
    pl_fn, _ = build_kernel_fn_pallas(kdef, N, 256, N)
    dt_x, dt_p = mandel_time(xla_fn), mandel_time(pl_fn)
    out["mandelbrot"] = {
        "xla_mpix_s": round(N / dt_x / 1e6, 1),
        "pallas_mpix_s": round(N / dt_p / 1e6, 1),
        "speedup": round(dt_x / dt_p, 2),
    }

    # n-body: leapfrog chain — positions drift by the updated velocities
    # between steps (the kernel itself updates velocities only, matching
    # the reference; a static-positions chain would let XLA hoist the
    # loop-invariant O(n^2) accel pass out of the rep loop)
    kdef = {k.name: k for k in lang.parse_kernels(NBODY_SRC)}["nBody"]
    narrs = tuple(
        jnp.asarray(rng.standard_normal(nbody_n).astype(np.float32))
        for _ in range(6)
    )
    nvals = (np.int32(nbody_n), np.float32(1e-4))
    dt_x, dt_p, match = faceoff(
        kdef, narrs, lambda j: nvals,
        rotate=lambda cur, o: (
            cur[0] + o[3] * 1e-4, cur[1] + o[4] * 1e-4, cur[2] + o[5] * 1e-4,
            o[3], o[4], o[5],
        ),
        nreps=nbody_reps,
    )
    gp = nbody_n * nbody_n / 1e9
    out["nbody"] = {
        "xla_gpairs_s": round(gp / dt_x, 3),
        "pallas_gpairs_s": round(gp / dt_p, 3),
        "speedup": round(dt_x / dt_p, 2),
        "match": match,
    }

    # wave: leapfrog chain (pnew -> p -> pold)
    kdef = {k.name: k for k in lang.parse_kernels(WAVE_SRC)}["wave"]
    warrs = tuple(
        jnp.asarray((rng.standard_normal(wave_n) * 0.5).astype(np.float32))
        for _ in range(3)
    )
    dt_x, dt_p, match = faceoff(
        kdef, warrs, lambda j: (),
        rotate=lambda cur, o: (o[2], cur[0], cur[1]),
        nreps=wave_reps,
    )
    out["wave_stencil"] = {
        "xla_ms": round(dt_x * 1e3, 3),
        "pallas_ms": round(dt_p * 1e3, 3),
        "xla_gelem_s": round(wave_n / dt_x / 1e9, 2),
        "pallas_gelem_s": round(wave_n / dt_p / 1e9, 2),
        "speedup": round(dt_x / dt_p, 2),
        "match": match,
    }
    return out


def marker_overhead(n: int = 4096, dispatches: int = 200) -> dict:
    """Per-dispatch host gap with fine-grained markers OFF vs ON — the
    reference quantifies this cost as 2-3 µs -> 150-200 µs per light
    kernel (ClNumberCruncher.cs:79; Cores.cs:447 says 200-300 µs).

    Methodology: a light kernel (tiny saxpy) dispatched ``dispatches``
    times in enqueue mode (no per-call sync — the loop measures pure host
    dispatch cost, which is what markers tax: every launch additionally
    increments the native counter and enqueues a completion join).  One
    barrier closes each run; its cost is excluded by timing only the
    dispatch loop.  Reported per-dispatch, best of 3 runs each."""
    from .hardware import chip_devices

    src = """
    __kernel void light(__global float* x, __global float* y, float a) {
        int i = get_global_id(0);
        y[i] = a * x[i] + y[i];
    }
    """
    # per-dispatch host cost is a per-lane quantity: one lane is clean
    devs = chip_devices().subset(1)
    # ckprove flag fix (partial-safe advisory): the light kernel reads
    # x only at [i], so each lane needs only its slice — the old full
    # read paid whole-array H2D per lane per dispatch in a benchmark
    # whose entire point is per-dispatch cost.  Bit-identity with the
    # full read is pinned by test_partial_read_fix_is_bit_identical.
    x = ClArray(np.arange(n, dtype=np.float32), name="mx",
                partial_read=True, read_only=True)
    y = ClArray(n, np.float32, name="my", partial_read=True)
    cr = NumberCruncher(devs, src)
    out: dict = {"dispatches": dispatches}
    try:
        cr.enqueue_mode = True
        for label, markers in (("markers_off", False), ("markers_on", True)):
            cr.fine_grained_queue_control = markers
            # warm (compile + caches), then measure the dispatch loop only
            for _ in range(8):
                x.next_param(y).compute(cr, 501, "light", n, 256, values=(1.0,))
            cr.barrier()
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(dispatches):
                    x.next_param(y).compute(
                        cr, 501, "light", n, 256, values=(1.0,)
                    )
                dt = (time.perf_counter() - t0) / dispatches
                cr.barrier()
                best = min(best, dt)
            out[label + "_us"] = round(best * 1e6, 1)
            if markers:
                cr.count_markers_remaining()  # exercise the query path
        out["marker_cost_us"] = round(
            out["markers_on_us"] - out["markers_off_us"], 1
        )
        out["reference_claim_us"] = "light-kernel gap 2-3 -> 150-200 (ClNumberCruncher.cs:79)"
    finally:
        cr.enqueue_mode = False
        cr.dispose()
    return out


def dispatch_floor_sweep(
    devices: Devices | None = None,
    ks: Sequence[int] = (1, 8, 32, 128),
    n: int = 1 << 14,
    local_range: int = 256,
    reps: int = 3,
    modes: Sequence[bool] = (False, True),
) -> dict:
    """Per-dispatch overhead vs enqueue-window size K, per-iteration vs
    FUSED dispatch — the measurement behind the dispatch-floor collapse
    (bench.py ``dispatch_floor`` section, tools/dispatch_floor.py CLI).

    Methodology: a light kernel (device work negligible next to the
    dispatch floor) runs windows of K computes + one barrier under the
    span tracer; per row the BEST of ``reps`` windows reports

    - ``per_dispatch_ms`` — (window wall − barrier fence) / K: the host
      cost each compute call pays.  On the per-iteration path this is
      the floor a window pays ~K times; on the fused
      path calls 2..K are counter increments and the ladder dispatches
      in batches, so it collapses toward wall/K of a few batched
      launches;
    - ``launch_spans`` / ``launch_ms`` — actual ladder dispatches seen
      by the tracer (the O(K) → O(K/fused_batch) evidence);
    - ``fence_ms`` — the barrier's fence span (excluded from the floor:
      it is the sync cost, not the dispatch cost; note the fused path
      dispatches late, so its fence absorbs device drain the
      per-iteration path paid during the window);
    - ``fused_windows`` — fused ladder flushes inside the window.

    Every row keeps the spans' own counts next to the derived number so
    a regression names its factor instead of hiding in an average."""
    from .hardware import chip_devices
    from .trace.attribution import window_report
    from .trace.spans import TRACER

    src = """
    __kernel void light(__global float* x) {
        int i = get_global_id(0);
        x[i] = x[i] + 1.0f;
    }
    """
    devs = devices if devices is not None else chip_devices()
    devs = devs.subset(1)  # the floor is per-lane host cost; 1 lane is clean
    out: dict = {
        "n": n,
        "reps": reps,
        "note": (
            "per_dispatch_ms = (window wall - barrier fence)/K, best of "
            f"{reps} windows; light kernel, device work negligible. "
            "fused rows defer calls 2..K and dispatch batched ladders — "
            "launch_spans is the dispatch-count evidence; their fence "
            "absorbs device drain the per-iteration path paid mid-window"
        ),
        "rows": [],
    }
    for fused in modes:
        cr = NumberCruncher(devs, src)
        cr.fused_dispatch = fused
        x = ClArray(np.zeros(n, np.float32), name="df", partial_read=True)
        was_tracing = TRACER.enabled
        try:
            cr.enqueue_mode = True
            # warm: compile both the per-call ladder and (fused mode) the
            # fused executable outside every timed window
            for _ in range(3):
                x.compute(cr, 551, "light", n, local_range)
            cr.barrier()
            if not was_tracing:
                TRACER.enable(clear=True)
            for K in ks:
                best = None
                for _ in range(max(1, reps)):
                    w0 = cr.cores.fused_stats["windows"]
                    t0 = time.perf_counter()
                    for _ in range(K):
                        x.compute(cr, 551, "light", n, local_range)
                    cr.barrier()
                    t1 = time.perf_counter()
                    rep = window_report(
                        TRACER.spans_between(t0, t1), t0, t1
                    )
                    fence = rep.per_kind.get("fence", {"ms": 0.0})["ms"]
                    launch = rep.per_kind.get(
                        "launch", {"ms": 0.0, "count": 0}
                    )
                    wall_ms = (t1 - t0) * 1e3
                    row = {
                        "fused": bool(fused),
                        "K": K,
                        "wall_ms": round(wall_ms, 3),
                        "fence_ms": round(fence, 3),
                        "per_dispatch_ms": round(
                            max(wall_ms - fence, 0.0) / K, 4
                        ),
                        "launch_spans": launch.get("count", 0),
                        "launch_ms": round(launch["ms"], 3),
                        "fused_windows": (
                            cr.cores.fused_stats["windows"] - w0
                        ),
                    }
                    if best is None or row["per_dispatch_ms"] < best[
                        "per_dispatch_ms"
                    ]:
                        best = row
                out["rows"].append(best)
            cr.enqueue_mode = False
        finally:
            if not was_tracing:
                TRACER.disable()
            if cr.enqueue_mode:
                cr.enqueue_mode = False
            cr.dispose()
    # headline ratio: the floor collapse at the largest K
    k_max = max(ks)
    per = {
        (r["fused"], r["K"]): r["per_dispatch_ms"] for r in out["rows"]
    }
    if (False, k_max) in per and (True, k_max) in per:
        out["floor_collapse_at_kmax"] = round(
            per[(False, k_max)] / max(per[(True, k_max)], 1e-6), 2
        )
    return out


def fori_chain_bench(step, args, reps, trials=3, carry=None):
    """Per-step seconds for ``step(*args) -> pytree`` at device throughput.

    The one dependent-chain harness (shared by bench.py's flash faceoff
    and the tools/ sweeps — the compiler traps were each found once and
    must stay fixed in ONE place):

    - the chain runs INSIDE one jitted ``lax.fori_loop`` (a python loop
      of dispatches adds the host's per-launch cost to every step); each
      iteration feeds EVERY output leaf back into the carry — when the
      output leaves pair up with the carry by shape (e.g. grads
      (dq, dk, dv) against (q, k, v)) each input is perturbed by its own
      gradient, otherwise every same-shaped carry takes the leading
      leaf.  Feeding back only one leaf would let XLA dead-code-eliminate
      the computations producing the others (the dkv backward kernel,
      the dense dk/dv einsums) right out of the loop;
    - ``carry`` overrides the feedback rule: ``carry(c, out) -> tuple``
      for steps whose natural chaining is structural (e.g. a stencil's
      output becomes the next input) rather than perturbative;
    - each trial closes with ``block_until_ready`` on the whole carry;
      the best of ``trials`` is reported.
    """
    import jax
    from jax import lax

    @jax.jit
    def chain(*a):
        def body(_, c):
            out = step(*c)
            if carry is not None:
                return tuple(carry(c, out))
            leaves = jax.tree_util.tree_leaves(out)
            if len(leaves) == len(c) and all(
                l.shape == x.shape for l, x in zip(leaves, c)
            ):
                return tuple(
                    x + 1e-6 * l.astype(x.dtype)
                    for x, l in zip(c, leaves)
                )
            # fallback: every same-shaped carry takes the LEADING leaf —
            # sound ONLY when that covers every output leaf.  A step with
            # extra output leaves (they'd be dropped → the computations
            # producing them DCE right out of the loop), no output leaves
            # at all, or a lead that matches no carry (the whole step
            # DCEs) is the exact elision trap this harness exists to
            # prevent — refuse loudly instead of silently benchmarking a
            # subset (ADVICE r5 #5)
            fed = (
                [x.shape == leaves[0].shape for x in c] if leaves else []
            )
            if len(leaves) != 1 or not any(fed):
                raise ValueError(
                    "fori_chain_bench fallback feedback would leave output "
                    f"leaves DCE-able: {len(leaves)} output leaf(s) vs "
                    f"{len(c)} carry leaf(s), shapes do not pair and only "
                    "the leading leaf would feed back — pass carry=(c, out)"
                    " -> tuple to define the chaining explicitly"
                )
            lead = leaves[0]
            return tuple(
                x + 1e-6 * lead.astype(x.dtype)
                if x.shape == lead.shape else x
                for x in c
            )
        return lax.fori_loop(0, reps, body, a)

    c = jax.block_until_ready(tuple(chain(*args)))
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(*c))
        best = min(best, (time.perf_counter() - t0) / reps)
    return best


def dtype_lowering_matrix(
    n: int = 4096,
    local_range: int = 256,
    budget_sec: float = 420.0,
) -> dict:
    """Systematic dtype × lowering × mode sweep ON THE CURRENT BACKEND
    (VERDICT r4 #5): the reference's Tester type grid
    (Tester.cs:6763-7065) as a driver-runnable gate, so the next
    Mosaic-only dtype break is a table cell, not a hand discovery.

    Per cell, a generator kernel ``b[i] = (ct)2 * a[i] + (ct)3`` declared
    in the dtype's ctype is compiled and matched against the numpy oracle
    computed in the same dtype:

    - ``xla`` / ``pallas``: the two kernel-language lowerings directly
      (Pallas with ``force=True`` — the routing veto is itself a recorded
      outcome, not an error);
    - ``harness``: the full ``compute()`` path (NumberCruncher + ClArray
      of the dtype) with the blob pipeline enabled.

    Cell outcomes: ``pass`` (matched the dtype-true oracle), ``pass-x32``
    (64-bit dtype in an x32 process — matched the x32-canonicalized
    oracle, the documented real-TPU regime), ``veto`` (PallasUnsupported:
    the measured routing policy refused, e.g. f16 off Mosaic),
    ``fail: <err>`` otherwise; cells after the soft ``budget_sec`` are
    ``skipped`` (a partial table beats a dead artifact).  The two
    ``mixed-*`` rows drive the r4 boundary contract (storage dtype ≠
    declared ctype: f16/bf16 arrays into a float-declared kernel)."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    from .kernel import codegen, lang
    from .kernel.pallas_backend import PallasUnsupported, build_kernel_fn_pallas

    x64 = bool(jax.config.jax_enable_x64)
    rows = [
        # (label, storage numpy dtype, declared ctype)
        ("int8", np.int8, "char"),
        ("uint8", np.uint8, "uchar"),
        ("int16", np.int16, "short"),
        ("int32", np.int32, "int"),
        ("uint32", np.uint32, "uint"),
        ("int64", np.int64, "long"),
        ("float32", np.float32, "float"),
        ("float64", np.float64, "double"),
        ("float16", np.float16, "half"),
        ("bfloat16", ml_dtypes.bfloat16, "float"),   # mixed-boundary row
        ("mixed-f16-float", np.float16, "float"),    # mixed-boundary row
    ]
    t_start = time.monotonic()
    table: dict = {label: {} for label, _, _ in rows}

    def oracle(a_host, storage, ct):
        # compute in the declared type, store back in the storage type —
        # the boundary contract (kernel/codegen.py _loaded/_store)
        decl_np = {
            "char": np.int8, "uchar": np.uint8, "short": np.int16,
            "int": np.int32, "uint": np.uint32, "long": np.int64,
            "float": np.float32, "double": np.float64, "half": np.float16,
        }[ct]
        if not x64 and decl_np in (np.int64, np.float64):
            decl_np = np.int32 if decl_np is np.int64 else np.float32
        acc = a_host.astype(decl_np) * decl_np(2) + decl_np(3)
        return acc.astype(storage)

    def prep(label, storage, ct):
        src = (
            f"__kernel void gen(__global {ct}* a, __global {ct}* b) "
            "{ int i = get_global_id(0); "
            f"b[i] = (({ct})2) * a[i] + (({ct})3); }}"
        )
        kdef = {k.name: k for k in lang.parse_kernels(src)}["gen"]
        rng = np.random.default_rng(7)
        a_host = rng.integers(0, 10, n).astype(storage)
        want = oracle(a_host, storage, ct)
        sdt = np.dtype(storage)
        want_x32 = want
        if not x64 and sdt.itemsize == 8:
            # the x32 process canonicalizes 64-bit payloads on device
            want_x32 = want.astype(
                np.int32 if sdt.kind in "iu" else np.float32
            )

        def match(got) -> str:
            got = np.asarray(got)
            ref = want_x32 if got.dtype != sdt else want
            if got.dtype == ref.dtype and np.array_equal(got, ref):
                return "pass" if got.dtype == sdt else "pass-x32"
            # SUB-32-bit float storage only (f16/bf16 and the mixed
            # rows): declared-type arithmetic may round differently on
            # the VPU — accept small error there.  f32/f64 cells compute
            # 2*a+3 on small ints, exactly representable, and must be
            # EXACT (ADVICE r5 #1: a 2%-wrong f32 cell must not 'pass').
            sub32_float = (
                np.issubdtype(ref.dtype, np.floating)
                and ref.dtype.itemsize < 4
            ) or str(ref.dtype) == "bfloat16"
            if sub32_float:
                err = np.abs(
                    got.astype(np.float64) - ref.astype(np.float64)
                ).max()
                tol = max(np.abs(ref.astype(np.float64)).max(), 1.0) * 2e-2
                if err <= tol:
                    return ("pass" if got.dtype == sdt else "pass-x32")
            return f"fail: mismatch (got {got.dtype}, want {ref.dtype})"

        return src, kdef, a_host, storage, match, label

    def lowered_cell(build, p):
        src, kdef, a_host, storage, match, label = p
        fn, _ = build(kdef, n, local_range, n)
        arrs = (jnp.asarray(a_host), jnp.zeros(n, jnp.asarray(a_host).dtype))
        out = jax.jit(fn)(0, arrs, ())
        return match(out[1])

    xla_cell = functools.partial(lowered_cell, codegen.build_kernel_fn)
    pallas_cell = functools.partial(
        lowered_cell,
        functools.partial(build_kernel_fn_pallas, force=True),
    )

    def harness_cell(p):
        from .hardware import chip_devices

        src, kdef, a_host, storage, match, label = p
        devs = chip_devices().subset(1)
        a = ClArray(a_host.copy(), name=f"dm_a_{label}",
                    partial_read=True, read_only=True)
        b = ClArray(np.zeros(n, storage), name=f"dm_b_{label}",
                    write_only=True)
        cr = NumberCruncher(devs, src)
        try:
            a.next_param(b).compute(
                cr, 7300, "gen", n, local_range,
                pipeline=True, pipeline_blobs=4,
            )
            return match(b.host())
        finally:
            cr.dispose()

    prepped = {label: prep(label, storage, ct) for label, storage, ct in rows}
    # MODE-major iteration: when the budget bites mid-sweep, full dtype
    # coverage of the earlier lowerings survives and only the trailing
    # mode column degrades — losing whole dtype ROWS (the r5 first cut's
    # dtype-major order) throws away exactly the breadth the table is for
    for mode, cell in (("xla", xla_cell), ("pallas", pallas_cell),
                       ("harness_pipelined", harness_cell)):
        for label, _, _ in rows:
            if time.monotonic() - t_start > budget_sec:
                table[label][mode] = "skipped (budget)"
                continue
            try:
                table[label][mode] = cell(prepped[label])
            except PallasUnsupported as e:
                table[label][mode] = f"veto: {e}"[:80]
            except Exception as e:  # noqa: BLE001 - the cell IS the report
                table[label][mode] = f"fail: {type(e).__name__}: {e}"[:120]

    n_pass = sum(
        1 for r in table.values() for v in r.values()
        if str(v).startswith("pass")
    )
    n_veto = sum(
        1 for r in table.values() for v in r.values()
        if str(v).startswith("veto")
    )
    n_fail = sum(
        1 for r in table.values() for v in r.values()
        if str(v).startswith("fail")
    )
    return {
        "backend": jax.default_backend(),
        "x64": x64,
        "cells_pass": n_pass,
        "cells_veto": n_veto,
        "cells_fail": n_fail,
        "table": table,
    }


def duplex_ceiling(n: int = 1 << 22, reps: int = 3) -> dict:
    """Host-link duplex capacity: pure H2D ∥ D2H with NO compute, against
    each direction alone — the physical ceiling for read/write overlap
    that the pipeline engines can never beat (VERDICT r3 #2: if this is
    < 0.9, achieved overlap must be judged against IT, not against 1.0).

    ceiling = (h2d + d2h - duplex) / (h2d + d2h - max(h2d, d2h)):
    1.0 = the link runs both directions concurrently at full rate;
    0.0 = fully serial link.  Fresh values every rep (a mutated host
    array for H2D, a freshly computed device array for D2H — a jax
    array caches its host copy after the first read-back)."""
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    host_a = np.arange(n, dtype=np.float32)
    base = jax.device_put(jnp.zeros(n, jnp.float32), dev)
    jax.block_until_ready(base)
    k = [0]

    def fresh_host():
        k[0] += 1
        host_a[0] = k[0]
        return host_a

    def fresh_dev():
        k[0] += 1
        y = base + np.float32(k[0])
        jax.block_until_ready(y)
        return y

    def t_h2d_once():
        h = fresh_host()
        t0 = time.perf_counter()
        jax.block_until_ready(jax.device_put(h, dev))
        return time.perf_counter() - t0

    def t_d2h_once():
        y = fresh_dev()
        t0 = time.perf_counter()
        np.asarray(y)
        return time.perf_counter() - t0

    def t_duplex_once():
        y = fresh_dev()
        h = fresh_host()
        t0 = time.perf_counter()
        x = jax.device_put(h, dev)  # async H2D
        np.asarray(y)               # D2H
        jax.block_until_ready(x)
        return time.perf_counter() - t0

    h2d = min(t_h2d_once() for _ in range(reps))
    d2h = min(t_d2h_once() for _ in range(reps))
    dup = min(t_duplex_once() for _ in range(reps))
    denom = h2d + d2h - max(h2d, d2h)
    ceiling = (h2d + d2h - dup) / denom if denom > 0 else 0.0
    ceiling = min(max(ceiling, 0.0), 1.0)  # jitter must not report >1
    gb = n * 4 / 1e9
    return {
        "h2d_ms": round(h2d * 1e3, 1),
        "d2h_ms": round(d2h * 1e3, 1),
        "duplex_ms": round(dup * 1e3, 1),
        "h2d_gbps": round(gb / max(h2d, 1e-9), 3),
        "d2h_gbps": round(gb / max(d2h, 1e-9), 3),
        "ceiling": round(ceiling, 3),
        "bytes": n * 4,
    }
