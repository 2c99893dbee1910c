"""Multi-host demo — one compute() spanning N processes over DCN.

Reference: the cluster tier (ClusterAccelerator.cs:170-355) driving
remote ``Cores`` over TCP.  This demo runs the TPU-pod idiom instead:
:class:`cekirdekler_tpu.cluster.DistributedAccelerator` — the same
``compute()`` surface spanning the processes of a ``jax.distributed``
job, with the LCM-step cluster balancer splitting the global range
across processes and written ranges exchanged by XLA collectives.

Self-launching: run with no arguments and it spawns ``--procs`` worker
copies of itself (each a separate OS process with its own virtual CPU
devices, joined through a coordinator on localhost), then waits for the
consolidated report.  On a real multi-host pod you would instead start
one copy per host with ``--worker <pid> --procs <N> --coordinator
<host:port>`` pointing every process at the same coordinator — the
worker path is exactly that program.

    python examples/dcn_cluster.py                  # 2 procs x 4 devices
    python examples/dcn_cluster.py --procs 4        # 4 procs x 4 devices

The workload: a skewed-cost kernel (items in the lower half of the range
iterate 8x longer), so the equal first split is WRONG and the balancer
must move work between processes.  Timing skew is real wall time here —
each process genuinely computes — and the report shows the share
trajectory converging.
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

SRC = """
__kernel void skewed(__global float* x, __global float* y, int n) {
    int i = get_global_id(0);
    int iters = (i < n / 2) ? 4000 : 500;
    float acc = x[i];
    for (int k = 0; k < iters; k++) {
        acc = acc + 0.25f;
    }
    y[i] = acc;
}
"""


def worker(pid: int, nproc: int, coordinator: str,
           devices_per_proc: int) -> None:
    # hand-launched workers (real pods) may not have the virtual-device
    # flag exported; set it before jax first initializes (best effort —
    # if something already imported jax this is a no-op and the
    # environment's device count wins)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{devices_per_proc}"
        ).strip()

    from cekirdekler_tpu.arrays.clarray import ClArray
    from cekirdekler_tpu.cluster import DistributedAccelerator
    from cekirdekler_tpu.cluster.dcn import initialize

    initialize(coordinator, nproc, pid)
    import jax

    acc = DistributedAccelerator()
    try:
        acc.setup_nodes(SRC)
        n = 16384
        calls = 8
        x = ClArray(np.arange(n, dtype=np.float32), partial_read=True,
                    read_only=True)
        y = ClArray(np.zeros(n, np.float32), partial_read=True,
                    write_only=True)
        t0 = time.perf_counter()
        traj = []
        for _ in range(calls):
            acc.compute("skewed", [x, y], compute_id=1, global_range=n,
                        local_range=64, values=(n,))
            traj.append(acc.ranges_of(1))
        wall = time.perf_counter() - t0
        # self-check: acc = x[i] + iters * 0.25, exact in f32
        iters = np.where(np.arange(n) < n // 2, 4000, 500)
        np.testing.assert_array_equal(
            np.asarray(y),
            np.arange(n, dtype=np.float32) + iters.astype(np.float32) * 0.25,
        )
        if pid == 0:
            print(f"[demo] {nproc} processes x "
                  f"{jax.local_device_count()} devices, n={n}, "
                  f"{calls} calls in {wall:.2f}s", flush=True)
            print(f"[demo] share trajectory (process 0's view):", flush=True)
            for i, r in enumerate(traj):
                print(f"  call {i}: {r}", flush=True)
            print(f"[demo] result exact on every process; timings "
                  f"{[f'{t:.0f}ms' for t in acc.compute_timing(1)]}",
                  flush=True)
        print(f"[worker {pid}] OK", flush=True)
    finally:
        acc.dispose()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", type=int, default=None)
    ap.add_argument("--procs", type=int, default=2)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--coordinator", default=None,
                    help="host:port of the coordinator (real multi-host "
                         "launches; defaults to localhost:--port)")
    ap.add_argument("--devices-per-proc", type=int, default=4)
    args = ap.parse_args()
    if args.worker is not None:
        if args.coordinator is None and args.port == 0:
            ap.error("hand-launched workers need --coordinator host:port "
                     "(or --port from the self-launching parent)")
        worker(args.worker, args.procs, args.coordinator or
               f"localhost:{args.port}", args.devices_per_proc)
        return
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    # every worker is a child PINNED to the CPU backend (a chip belongs to
    # one process; these are virtual CPU devices, and the output says so)
    print(f"dcn_cluster: {args.procs} worker processes x "
          f"{args.devices_per_proc} virtual CPU devices (JAX_PLATFORMS=cpu)")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.devices_per_proc}"
    )
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--worker", str(pid), "--procs", str(args.procs),
             "--port", str(port),
             "--devices-per-proc", str(args.devices_per_proc)],
            env=env,
        )
        for pid in range(args.procs)
    ]
    # a worker killed by a signal has a NEGATIVE returncode — any nonzero
    # exit (either sign) must fail the demo, and a hung worker (e.g. the
    # coordinator never formed) must not block forever or leave orphans
    rc = 0
    try:
        deadline = time.monotonic() + 600
        for p in procs:
            remaining = max(1.0, deadline - time.monotonic())
            try:
                if p.wait(timeout=remaining) != 0:
                    rc = 1
            except subprocess.TimeoutExpired:
                rc = 1
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    pass  # keep killing the rest; the OS reaps on exit
    sys.exit(rc)


if __name__ == "__main__":
    main()
