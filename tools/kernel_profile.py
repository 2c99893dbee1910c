#!/usr/bin/env python
"""Per-kernel device-timeline profiles from the command line.

Three modes, all built on ``cekirdekler_tpu.trace.device``:

- **run** (default): drive an annotated framework workload (mandelbrot
  through the full ``compute()`` scheduler) under a device-attribution
  capture and print the reconciled per-kernel report — device wall, op
  counts, idle gaps, coverage fraction.  On CPU-only rigs the report is
  a NAMED absence (the capture machinery, marks included, still
  exercises end-to-end).
- **--trace-dir D**: analyze an existing Xprof/trace-event dump (a real
  rig's capture, or a synthetic fixture) without running anything.
- **--show-store**: list the persistent kernel-profile store's keys and
  each key's best row.

Options::

    python tools/kernel_profile.py [--size N] [--iters K]
        [--trace-dir D] [--chrome OUT.json] [--json]
        [--store DIR] [--show-store] [--flops F --bytes B]

``--chrome`` writes the UNIFIED Perfetto trace: host spans and device
ops side by side on one clock.  ``--flops``/``--bytes`` add a roofline
row (defaults to the v5e peaks; see ``--peak-tflops``/``--peak-gbps``).
``--store DIR`` persists one row per profiled kernel keyed by
(kernel, shape, ladder-blocks signature) — the store a block-shape
autotuner reads.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _print_report(rep, as_json: bool) -> None:
    if as_json:
        from cekirdekler_tpu.utils.jsonsafe import json_safe

        print(json.dumps(json_safe(rep.to_dict()), indent=2,
                         allow_nan=False))
    else:
        print(rep.table())
        if rep.anchor:
            print(f"clock anchor: {rep.anchor}; matched_by: "
                  f"{dict(rep.matched_by)}")


def analyze_dir(args) -> int:
    """--trace-dir mode: reduce an existing dump (no host marks — the
    dump's own ``ck/launch`` mark events drive the correlation)."""
    from cekirdekler_tpu.trace.device import correlate, parse_trace_dump

    dump = parse_trace_dump(args.trace_dir)
    rep = correlate(dump)
    _print_report(rep, args.json)
    _maybe_roofline(rep, args)
    _maybe_chrome(rep, [], [], args)
    _maybe_store(rep, args, shape=("trace-dir",), blocks=("as-captured",))
    return 0


def run_workload(args) -> int:
    """Default mode: annotated mandelbrot through the full scheduler
    under a capture on the current rig."""
    import numpy as np

    import cekirdekler_tpu as ct
    from cekirdekler_tpu.arrays.clarray import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.core.stream import plan_signature
    from cekirdekler_tpu.core.worker import _ladder
    from cekirdekler_tpu.trace import TRACER
    from cekirdekler_tpu.trace.device import DeviceCapture
    from cekirdekler_tpu.workloads import mandelbrot_pallas_kernel

    devs = ct.chip_devices().subset(1)
    print("device:", devs[0].jax_device,
          f"(platform {devs[0].platform}; Pallas lowers for it)")

    n = args.size * args.size
    local = 256
    vals = (-2.0, -1.25, 2.5 / args.size, 2.5 / args.size, args.size, 64)
    cr = NumberCruncher(devs, mandelbrot_pallas_kernel())
    out = ClArray(n, np.float32, name="kp_out", read=False, write=True)
    try:
        out.compute(cr, 7100, "mandelbrot", n, local, values=vals)  # warm
        cr.barrier()
        TRACER.enable(clear=True)
        cap = DeviceCapture(args.capture_dir)
        with cap:
            cr.enqueue_mode = True
            for _ in range(args.iters):
                out.compute(cr, 7100, "mandelbrot", n, local, values=vals)
            cr.barrier()
            cr.enqueue_mode = False
        spans = TRACER.snapshot()
        TRACER.disable()
        rep = cap.report
        _print_report(rep, args.json)
        _maybe_roofline(rep, args)
        _maybe_chrome(rep, spans, cap.marks.snapshot(), args)
        _maybe_store(
            rep, args, shape=(n,),
            blocks=(plan_signature(_ladder(n, local)),),
        )
        return 0
    finally:
        cr.enqueue_mode = False
        cr.dispose()


def _maybe_roofline(rep, args) -> None:
    if args.flops is None or args.bytes is None or rep.absent:
        return
    from cekirdekler_tpu.trace.device import roofline_row

    for prof in sorted(rep.kernels, key=lambda k: -k.device_ms):
        row = roofline_row(args.flops, args.bytes, prof.device_ms,
                           peak_tflops=args.peak_tflops,
                           peak_gbps=args.peak_gbps)
        print(f"roofline {prof.kernel}: {row['attained_tflops']} Tflop/s "
              f"({row['bound']}-bound, intensity "
              f"{row['intensity_flop_per_byte']} flop/B, mfu {row['mfu']}, "
              f"{row['frac_of_roof']:.0%} of roof)")


def _maybe_chrome(rep, spans, marks, args) -> None:
    if not args.chrome:
        return
    from cekirdekler_tpu.trace.device import unified_chrome_trace
    from cekirdekler_tpu.utils.jsonsafe import json_safe

    doc = unified_chrome_trace(spans, rep, ops=rep.ops, marks=marks,
                               process_name="kernel_profile")
    with open(args.chrome, "w") as f:
        json.dump(json_safe(doc), f, allow_nan=False)
    print(f"unified chrome trace ({len(spans)} host spans, "
          f"{len(rep.ops)} device ops) -> {args.chrome}")


def _maybe_store(rep, args, shape, blocks) -> None:
    if not args.store or rep.absent:
        return
    from cekirdekler_tpu.trace.device import ProfileStore

    store = ProfileStore(args.store)
    for prof in rep.kernels:
        path = store.put(prof.kernel, shape, blocks, {
            "device_ms": round(prof.device_ms, 3),
            "op_count": prof.op_count,
            "launches": prof.launches,
            "idle_ms": round(prof.idle_ms, 3),
            "coverage_frac": round(rep.coverage_frac, 4),
        })
        print(f"stored {prof.kernel} -> {path}")


def _tuner_vs_best(store, best) -> str:
    """The per-key honesty column (``tools/block_sweep.py``'s
    ``choice_vs_optimum`` idiom): what the block tuner would ENGAGE for
    this key — store-seeded, clamped to the legal tile grid — next to
    the store's own best row, so a tuner that cannot cash in a
    persisted profile is visible right where the profile lives."""
    from cekirdekler_tpu.core.blocktuner import BlockTuner

    sig, shape = best.get("kernel_sig"), best.get("shape")
    blocks = best.get("blocks")
    if not (sig and isinstance(shape, list) and shape
            and isinstance(blocks, list) and len(blocks) >= 2
            and all(isinstance(b, int) for b in blocks[:2])):
        return "tuner: n/a (non-tile key)"
    t = int(shape[1]) if len(shape) >= 2 else int(shape[0])
    tuner = BlockTuner(store=store)
    choice = tuner.choose(sig, t, t, shape=tuple(shape))
    stored = (int(blocks[0]), int(blocks[1]))
    # disagreement is either the store's cross-key global best winning
    # over this key's row, or grid-legality clamping — both honest
    verdict = "agree" if choice == stored else (
        "dense-fallback" if choice is None else "differs")
    return f"tuner {choice} vs store best {stored} [{verdict}]"


def show_store(args) -> int:
    from cekirdekler_tpu.trace.device import ProfileStore

    store = ProfileStore(args.store)
    if not store.enabled:
        print("kernel_profile: no store configured (pass --store DIR or "
              "set CK_PROFILE_STORE)", file=sys.stderr)
        return 1
    keys = store.keys()
    print(f"store {store.root}: {len(keys)} key(s)")
    for fn in keys:
        rows = store.read_key(fn)
        if not rows:
            print(f"  {fn}: (no parseable rows)")
            continue
        best = ProfileStore.best_row(rows) or rows[-1]
        print(f"  {fn}: {len(rows)} row(s), best device_ms="
              f"{best.get('device_ms')} (kernel {best.get('kernel_sig')}, "
              f"shape {best.get('shape')}, blocks {best.get('blocks')}); "
              f"{_tuner_vs_best(store, best)}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--size", type=int, default=256,
                    help="mandelbrot width=height for run mode "
                         "(default 256)")
    ap.add_argument("--iters", type=int, default=4,
                    help="enqueue iterations under capture (default 4)")
    ap.add_argument("--trace-dir", default=None,
                    help="analyze an existing trace dump instead of "
                         "running a workload")
    ap.add_argument("--capture-dir", default="/tmp/ck_kernel_profile",
                    help="where run mode writes its capture")
    ap.add_argument("--chrome", metavar="PATH", default=None,
                    help="write the unified host+device Perfetto trace")
    ap.add_argument("--json", action="store_true",
                    help="print the report as JSON")
    ap.add_argument("--store", metavar="DIR", default=None,
                    help="kernel-profile store directory (default: "
                         "$CK_PROFILE_STORE)")
    ap.add_argument("--show-store", action="store_true",
                    help="list the store's keys and best rows, then exit")
    ap.add_argument("--flops", type=float, default=None,
                    help="analytic flop count for the roofline row")
    ap.add_argument("--bytes", type=float, default=None,
                    help="analytic byte count for the roofline row")
    ap.add_argument("--peak-tflops", type=float, default=None,
                    help="machine compute peak (default: the running "
                         "device's, by kind — hardware.DEVICE_PEAKS)")
    ap.add_argument("--peak-gbps", type=float, default=None,
                    help="machine HBM bandwidth (default: same table)")
    args = ap.parse_args(argv)

    if args.show_store:
        return show_store(args)
    if args.trace_dir:
        return analyze_dir(args)
    return run_workload(args)


if __name__ == "__main__":
    sys.exit(main())
