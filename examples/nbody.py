"""N-body demo — the reference's flagship numeric workload, TPU-style.

Reference: ``Tester.nBody`` (Tester.cs:7682-7799) — n particles, direct
O(n²) gravity, 150 load-balanced iterations, velocity updates checked
against a host loop within ±0.01f; also the micro-benchmark behind the
device-ranking DSL (ClObjectApi.cs:1222-1244).  Here the same program as
a standalone demo: the C-subset kernel (workloads.NBODY_SRC) runs through
``NumberCruncher`` + ``ClArray.compute()`` with the iterative balancer
splitting bodies across every selected chip, leapfrog integration on the
host arrays between steps, a velocity-magnitude readout, and the ±0.01
host check on step one.

On TPU the kernel's inner ``x[j]`` loop takes the Pallas uniform-gather
path (SMEM operand; kernel/pallas_backend.py) — ~25× the vectorized XLA
lowering of the same source, and faster than the hand-written jnp
formulation (ops/nbody.py).

Run it on the chip, or on the host CPU when you say so (with no TPU and
no ``JAX_PLATFORMS=cpu`` it fails instead of quietly switching):

    python examples/nbody.py                       # TPU chip
    JAX_PLATFORMS=cpu python examples/nbody.py     # host CPU
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import cekirdekler_tpu as ct  # noqa: E402
from cekirdekler_tpu import ClArray  # noqa: E402
from cekirdekler_tpu.core.cruncher import NumberCruncher  # noqa: E402
from cekirdekler_tpu.workloads import NBODY_SRC, nbody_host_step  # noqa: E402

N = 4096
DT = 1e-3
STEPS = 25
LOCAL = 256


def main() -> int:
    devs = ct.chip_devices()
    print(f"devices: {[str(d) for d in devs]}")

    rng = np.random.default_rng(0)
    pos = (rng.random((3, N), dtype=np.float32) - 0.5) * 2.0
    x = ClArray(pos[0].copy(), name="x", read_only=True)
    y = ClArray(pos[1].copy(), name="y", read_only=True)
    z = ClArray(pos[2].copy(), name="z", read_only=True)
    vel = [ClArray(N, np.float32, name=f"v{c}", partial_read=True)
           for c in "xyz"]

    cr = NumberCruncher(devs, NBODY_SRC)
    group = x.next_param(y, z, *vel)  # built once, reused per step
    try:
        t0 = None  # starts AFTER step 0 (JIT compile + host check excluded)
        for step in range(STEPS):
            if step == 1:
                t0 = time.perf_counter()
            # one balanced velocity update across all chips
            group.compute(cr, 42, "nBody", N, LOCAL, values=(N, DT))
            if step == 0:
                # the reference's ±0.01f host check, on the first step
                exp = nbody_host_step(
                    pos[0], pos[1], pos[2],
                    np.zeros(N, np.float32), np.zeros(N, np.float32),
                    np.zeros(N, np.float32), DT,
                )
                err = max(
                    np.abs(vel[i].host() - exp[i]).max() for i in range(3)
                )
                status = "OK" if err < 0.01 else "FAIL"
                print(f"step 1 host check: maxerr={err:.2e}  [{status}]")
                if status == "FAIL":
                    return 1
            # leapfrog drift on the host arrays (they re-upload next step)
            for arr, v in zip((x, y, z), vel):
                arr.host()[:] += v.host() * DT
        dt = time.perf_counter() - t0
        timed_steps = STEPS - 1
        ranges = cr.ranges_of(42)
        gpairs = N * N * timed_steps / dt / 1e9
        vmag = np.sqrt(sum(v.host().astype(np.float64) ** 2 for v in vel))
        print(f"{timed_steps} timed steps x {N} bodies in {dt:.2f}s "
              f"({gpairs:.2f} Gpairs/s incl. host drift + transfers)")
        print(f"balancer ranges: {ranges} (sum {sum(ranges)})")
        print(f"mean |v| = {vmag.mean():.4f}, max |v| = {vmag.max():.4f}")
        print("nbody demo: OK")
        return 0
    finally:
        cr.dispose()


if __name__ == "__main__":
    sys.exit(main())
