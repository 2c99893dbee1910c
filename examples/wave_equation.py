"""Wave-equation demo — the reference's Unity mesh demo, TPU-style.

Reference: Kamera.cs:190-268 — a sphere mesh deformed every frame by a
``waveEquation`` kernel through ClNumberCruncher + ClArray.  Here the same
idea as a standalone program: a 2-D membrane simulated by a C-subset
kernel, stepped N times through a :class:`DevicePipeline` whose INTERNAL
arrays keep the field state device-resident across generations, with live
readback of every frame (the OUTPUT array), an ASCII render, and a numpy
reference check.

Run it on the chip, or on the host CPU when you say so (with no TPU and
no ``JAX_PLATFORMS=cpu`` it fails instead of quietly switching):

    python examples/wave_equation.py                     # TPU chip
    JAX_PLATFORMS=cpu python examples/wave_equation.py   # host CPU
    python examples/wave_equation.py --lanes 4           # split by range

``--lanes N`` runs the same kernels a second way, through ``compute()`` on
ONE ``NumberCruncher`` over N devices (upstream's headline use: the range
split by the balancer): the steps are enqueued in windows, the field stays
on the devices, and because every lane reads a row of ``u1`` that its
neighbour wrote the step before, the lanes exchange those rows device to
device between two steps (docs/KERNEL_LANGUAGE.md, "Reads across lanes").

The kernel uses shifted neighbor loads (``u[i-1]``, ``u[i+W]``) — outside
the elementwise Pallas subset, so it exercises the vectorized XLA lowering
(kernel/codegen.py padded-view slice loads) on every backend.
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import cekirdekler_tpu as ct  # noqa: E402
from cekirdekler_tpu import ClArray
from cekirdekler_tpu.core.cruncher import NumberCruncher
from cekirdekler_tpu.pipeline.device_pipeline import DevicePipeline, PipelineStage

W, H = 96, 48        # membrane grid (flattened row-major)
C2 = 0.22            # (c·dt/dx)^2 — stability requires < 0.5 in 2-D
STEPS = 120
LOCAL = 64

# One work item per cell.  u0 = field at t-1, u1 = field at t; the step
# kernel writes t+1 into `frame` (the OUTPUT the host reads every push),
# then `rotate` shifts the time window (u0 <- u1 <- frame) so state stays
# device-resident across generations (ArrayRole.INTERNAL).
WAVE_SRC = """
__kernel void waveStep(__global float* u0, __global float* u1,
                       __global float* frame,
                       int width, int height, float c2) {
    int i = get_global_id(0);
    int x = i % width;
    int y = i / width;
    if (x == 0 || x == width - 1 || y == 0 || y == height - 1) {
        frame[i] = 0.0f;    /* clamped boundary */
    } else {
        float lap = u1[i - 1] + u1[i + 1] + u1[i - width] + u1[i + width]
                    - 4.0f * u1[i];
        frame[i] = 2.0f * u1[i] - u0[i] + c2 * lap;
    }
}
__kernel void rotate(__global float* u0, __global float* u1,
                     __global float* frame,
                     int width, int height, float c2) {
    int i = get_global_id(0);
    u0[i] = u1[i];
    u1[i] = frame[i];
}
"""


def host_reference(u0: np.ndarray, u1: np.ndarray, steps: int) -> np.ndarray:
    """Numpy reference for the same scheme (self-check, the Tester.nBody
    pattern: Tester.cs:7682-7799)."""
    a = u0.reshape(H, W).astype(np.float64).copy()
    b = u1.reshape(H, W).astype(np.float64).copy()
    for _ in range(steps):
        lap = np.zeros_like(b)
        lap[1:-1, 1:-1] = (
            b[1:-1, :-2] + b[1:-1, 2:] + b[:-2, 1:-1] + b[2:, 1:-1]
            - 4.0 * b[1:-1, 1:-1]
        )
        c = 2.0 * b - a + C2 * lap
        c[0, :] = c[-1, :] = 0.0
        c[:, 0] = c[:, -1] = 0.0
        a, b = b, c
    return b.reshape(-1).astype(np.float32)


def ascii_frame(field: np.ndarray) -> str:
    """Coarse ASCII render of the membrane (the demo's 'mesh view')."""
    shades = " .:-=+*#%@"
    img = field.reshape(H, W)[::4, ::2]
    lo, hi = img.min(), img.max()
    span = (hi - lo) or 1.0
    rows = []
    for row in img:
        idx = ((row - lo) / span * (len(shades) - 1)).astype(int)
        rows.append("".join(shades[k] for k in idx))
    return "\n".join(rows)


def run_split(lanes: int, u0_init: np.ndarray, u1_init: np.ndarray) -> None:
    """The same steps through ``compute()``, the range split over ``lanes``
    devices: ``partial_read`` sends each lane its own rows (and the one row
    of ``u1`` beyond them that the kernel reads: the analysis proves the
    reach), windows of 20 steps closed by a barrier."""
    devices = ct.chip_devices().subset(lanes)
    if len(devices) < lanes:
        raise SystemExit(f"--lanes {lanes}: only {len(devices)} device(s) "
                         "here (on the host CPU: XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    u0 = ClArray(u0_init.copy(), name="u0", partial_read=True)
    u1 = ClArray(u1_init.copy(), name="u1", partial_read=True)
    frame = ClArray(W * H, np.float32, name="frame", read=False)
    cr = NumberCruncher(devices, WAVE_SRC)
    try:
        cr.enqueue_mode = True
        for step in range(STEPS):
            u0.next_param(u1, frame).compute(
                cr, 1, "waveStep rotate", W * H, LOCAL, values=(W, H, C2))
            if (step + 1) % 20 == 0:
                cr.barrier()
        cr.enqueue_mode = False  # flush: the field comes back to the host
        ranges = cr.ranges_of(1)
    finally:
        cr.dispose()
    err = float(np.abs(u1.host() - host_reference(u0_init, u1_init,
                                                  STEPS)).max())
    print(f"compute() over {lanes} lanes, ranges {ranges}: "
          f"max |device - host reference| {err:.3e}")
    assert err < 1e-3, "the split simulation diverged from the host reference"


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--lanes", type=int, default=0,
                    help="also run through compute() over this many devices")
    args = ap.parse_args()
    dev = ct.chip_devices()[0]
    print(f"wave_equation: {W}x{H} membrane, {STEPS} steps on {dev.name}")

    # initial condition: a gaussian pluck off-center
    yy, xx = np.mgrid[0:H, 0:W]
    bump = np.exp(-(((xx - W // 3) ** 2) / 18.0 + ((yy - H // 2) ** 2) / 18.0))
    u1_init = (0.6 * bump).reshape(-1).astype(np.float32)
    u0_init = u1_init.copy()  # zero initial velocity

    u0 = ClArray(u0_init.copy(), name="u0")
    u1 = ClArray(u1_init.copy(), name="u1")
    frame = ClArray(W * H, np.float32, name="frame")

    stage = PipelineStage(
        WAVE_SRC, "waveStep rotate", global_range=W * H, local_range=LOCAL,
        values=(W, H, C2),
    )
    stage.add_hidden(u0)
    stage.add_hidden(u1)
    stage.add_output(frame)

    pipe = DevicePipeline.make([stage], dev)
    out = np.zeros(W * H, np.float32)
    energy = []
    for step in range(STEPS):
        pipe.push(None, out)  # live readback every generation
        energy.append(float(np.square(out).sum()))
    pipe.dispose()

    want = host_reference(u0_init, u1_init, STEPS)
    err = float(np.abs(out - want).max())
    print(f"max |device - host reference| after {STEPS} steps: {err:.3e}")
    assert err < 1e-3, "device simulation diverged from the host reference"
    print(f"field energy: start {energy[0]:.4f} -> end {energy[-1]:.4f}")
    print(ascii_frame(out))
    if args.lanes:
        run_split(args.lanes, u0_init, u1_init)
    print("OK")


if __name__ == "__main__":
    main()
