"""Localize the vs_tuned_loop gap — now on top of ``cekirdekler_tpu.trace``.

Times the framework mandelbrot path against the hand-written Pallas loop,
then peels the framework's layers one at a time (direct launcher-fn loop,
compute() with launch skipped) so overhead lands on a named component
(methodology behind VERDICT r2 #2).  Where the original printed four
stopwatch numbers and left the decomposition to the reader, each framework
segment now runs under the span tracer and prints a full "where did the
time go" attribution table (launch dispatch vs upload vs fence vs
scheduler residue vs unexplained host gap), and ``--chrome PATH`` dumps
the whole session as a Chrome trace (chrome://tracing / Perfetto) for
visual inspection.

Run on the TPU chip: ``python tools/profile_gap.py [--chrome out.json]``
(through the chip tool; with no TPU it fails unless ``JAX_PLATFORMS=cpu``
asks for the host CPU, and the header line names the platform it ran on).
Not measured on today's code; see the ledger.
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def fence(x):
    x.block_until_ready()


def timed_segment(label, fn_iter, fence_out, n, iters, warmup, sync_every,
                  tracer=None):
    """Run one measured segment; when ``tracer`` is given, the timed
    window is attributed from its spans and the table printed under the
    stopwatch line."""
    from cekirdekler_tpu.trace.attribution import window_report

    out = fn_iter()
    fence_out(out)
    if tracer is not None:
        tracer.enable(clear=True)
    times = []
    t_lo = time.perf_counter()
    for k in range(warmup + iters):
        t0 = time.perf_counter()
        out = fn_iter()
        if (k + 1) % sync_every == 0 or k == warmup + iters - 1:
            fence_out(out)
        if k >= warmup:
            times.append((time.perf_counter() - t0) * 1000.0)
        elif k == warmup - 1:
            fence_out(out)
            t_lo = time.perf_counter()  # attribution covers the timed part
    t_hi = time.perf_counter()
    mpix = (n * len(times)) / (sum(times) / 1000.0) / 1e6
    print(f"{label:40s} {sum(times)/len(times):8.3f} ms/iter  {mpix:8.1f} Mpix/s")
    if tracer is not None:
        spans = tracer.spans_between(t_lo, t_hi)
        rep = window_report(
            spans, t_lo, t_hi,
            ring_wrapped=tracer.total_recorded > tracer.capacity,
            dropped_spans=tracer.dropped_spans,
        )
        print("  -- attribution " + "-" * 56)
        for line in rep.table().splitlines():
            print("  " + line)
        tracer.disable()
    return mpix


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chrome", metavar="PATH", default=None,
                    help="dump the full session as a Chrome trace JSON")
    ap.add_argument("--size", type=int, default=2048,
                    help="image width=height (default 2048; shrink for a "
                         "CPU smoke run — interpreted Pallas is slow)")
    ap.add_argument("--iters", type=int, default=32,
                    help="timed iterations per segment (default 32, min 1)")
    args_cli = ap.parse_args()
    args_cli.iters = max(1, args_cli.iters)

    import jax

    import cekirdekler_tpu as ct
    from cekirdekler_tpu.arrays.clarray import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.ops.mandelbrot import mandelbrot_pallas
    from cekirdekler_tpu.trace import TRACER, save_chrome_trace
    from cekirdekler_tpu.workloads import mandelbrot_pallas_kernel

    devs = ct.chip_devices().subset(1)
    dev = devs[0].jax_device
    print("device:", dev, f"(platform {dev.platform}; Pallas lowers for it)")

    width = height = args_cli.size
    n = width * height
    max_iter = 256
    iters, warmup, sync_every = args_cli.iters, 4, 16
    args = dict(
        n=n, x0=-2.0, y0=-1.25, dx=2.5 / width, dy=2.5 / height,
        width=width, max_iter=max_iter,
    )
    all_spans = []  # accumulated for --chrome across segments

    def seg(label, fn_iter, fence_out, traced):
        mpix = timed_segment(
            label, fn_iter, fence_out, n, iters, warmup, sync_every,
            tracer=TRACER if traced else None,
        )
        if traced:
            all_spans.extend(TRACER.snapshot())
        return mpix

    # layer 0: the hand-written ceiling — no framework, nothing to trace
    seg("tuned pallas loop", lambda: mandelbrot_pallas(**args), fence, False)

    # layer 1: the compiled launcher fn alone (kernel registry, no
    # scheduler) — still untraced, the framework spans start below
    src = mandelbrot_pallas_kernel()
    cr = NumberCruncher(devs, src)
    vals = (-2.0, -1.25, 2.5 / width, 2.5 / height, width, max_iter)
    fn, _ = cr.program.launcher("mandelbrot", n, 256, n)
    import jax.numpy as jnp

    state = {"buf": jax.device_put(jnp.zeros(n, jnp.float32), dev)}

    def launcher_iter():
        out = fn(0, (state["buf"],), vals)
        state["buf"] = out[0]
        return out[0]

    seg("direct launcher fn", launcher_iter, fence, False)

    # layer 2: the full compute() scheduler in enqueue mode — traced:
    # the table splits its per-iter cost into launch dispatch / upload /
    # fence / scheduler residue / host gap
    out_arr = ClArray(n, np.float32, name="mandel_out", read=False, write=True)
    cr.enqueue_mode = True

    def fw_iter():
        out_arr.compute(cr, 7001, "mandelbrot", n, 256, values=vals)

    def fw_fence(_):
        cr.barrier()

    seg("framework compute() enqueue", fw_iter, fw_fence, True)

    # layer 3: scheduler with the launch skipped — what's left is the
    # framework's own bookkeeping (the traced table should show near-zero
    # launch time and the same scheduler/fence costs)
    cr.no_compute_mode = True
    seg("framework no_compute (sched only)", fw_iter, fw_fence, True)
    cr.no_compute_mode = False

    # idle sync-point costs: the barrier is ONE fused probe per chip and
    # must price like a raw fence (1 RTT) — if these diverge, the barrier
    # regressed to O(buffers)
    cr.barrier()
    TRACER.enable(clear=True)
    t0 = time.perf_counter()
    for _ in range(8):
        cr.barrier()
    print(f"{'barrier (idle) x8':40s} {(time.perf_counter()-t0)/8*1000:8.3f} ms/call")
    all_spans.extend(TRACER.snapshot())
    TRACER.disable()
    t0 = time.perf_counter()
    for _ in range(8):
        fence(state["buf"])
    print(f"{'raw fence (idle) x8':40s} {(time.perf_counter()-t0)/8*1000:8.3f} ms/call")

    if args_cli.chrome:
        all_spans.sort(key=lambda s: s.t0)
        path = save_chrome_trace(all_spans, args_cli.chrome,
                                 process_name="profile_gap")
        print(f"chrome trace ({len(all_spans)} spans) -> {path}")

    cr.enqueue_mode = False
    cr.dispose()


if __name__ == "__main__":
    main()
