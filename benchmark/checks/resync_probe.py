#!/usr/bin/env python3
"""One untraced run of a cell with the program's span ring on, cut into the
parts of the flush that follows a range move: a look for what a process in
``mandelbrot_balance_4chip``'s slow mode does differently (PERF.md s.7).

    python3 benchmark/checks/resync_probe.py --workload mandelbrot_balance_4chip \
        --seed 11 --seconds 8

The ring costs a list store a span (no profiler session): the calls' walls
are the run's own.  Prints one line a part (median, p05, p95 over the
window's flushes), the pieces' issue-to-landed and landed-to-copied times by
lane, each lane's piece count, and the calls' walls with and without a flush.
Not a check and not a yardstick: nothing reads its output but a builder.
"""

import argparse
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def stats(ms: list) -> str:
    if not ms:
        return "none"
    s = sorted(ms)
    q = lambda f: s[min(len(s) - 1, int(f * len(s)))]
    return (f"n={len(s)} p05={q(0.05):.3f} p50={statistics.median(s):.3f} "
            f"p95={q(0.95):.3f} max={s[-1]:.3f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--flush-every-call", action="store_true",
                    help="a windowed cell on one lane never flushes inside "
                         "its window: leave and enter enqueue mode after "
                         "every call, so that each ends with the flush's "
                         "read-back (the path a range move takes)")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import cells
    import run

    import cekirdekler_tpu as ct
    from cekirdekler_tpu.trace.spans import TRACER

    cell = cells.load_cell(args.workload)
    seen = {}
    window, read_back = run.window, run.read_back

    def ringed_window(ctx, seconds, compiles):
        if args.flush_every_call:
            call = ctx.call

            def flushed() -> None:
                call()
                ctx.cell.loop.leave(ctx)
                ctx.cell.loop.enter(ctx)

            ctx.call = flushed
            for _ in range(3):  # the flush's own shapes, outside the window
                flushed()
        TRACER.enable(capacity=1 << 20)
        seen["t0"] = run.time.perf_counter()
        window(ctx, seconds, compiles)
        seen["t1"] = run.time.perf_counter()
        seen["spans"] = TRACER.snapshot()
        TRACER.disable()

    def kept_read_back(ctx):
        seen["pieces"] = [dict(getattr(w, "_piece_counts", {}))
                          for w in ctx.cr.cores.workers]
        seen["walls"] = list(ctx.walls)
        seen["ranges"] = list(ctx.ranges_log)
        return read_back(ctx)

    run.window, run.read_back = ringed_window, kept_read_back
    result = run.run_cell(cell, args.seed, args.seconds, False,
                          ct.all_devices().tpus())
    spans = [s for s in seen["spans"] if seen["t0"] <= s.t0 <= seen["t1"]]
    spans.sort(key=lambda s: s.t0)
    print(f"probe: correct {result['correct']} "
          + " ".join(f"{k}={v['value']:.6g}"
                     for k, v in result["metrics"].items()))
    walls, ranges = seen["walls"], seen["ranges"]
    moved = [ranges[i] != ranges[i + 1] for i in range(len(walls))]
    # the flush of a move comes at the NEXT call's first compute
    after_move = [False] + moved[:-1]
    print("probe: calls whose ranges moved at the barrier:", sum(moved),
          "of", len(walls))
    print("probe: walls ms after a move   ",
          stats([w * 1e3 for w, m in zip(walls, after_move) if m]))
    print("probe: walls ms after no move  ",
          stats([w * 1e3 for w, m in zip(walls, after_move) if not m]))
    print("probe: piece counts by lane", [list(p.values())
                                          for p in seen["pieces"]])
    print("probe: last ranges", ranges[-1])
    marks = [s for s in spans if s.kind == "resync"]
    parts = {"locks": [], "issue": [], "join": [], "reset": []}
    order = ["part:locks", "part:issue", "part:join", "part:reset"]
    whole = [s for s in marks if s.t1 > s.t0]
    ends = sorted(s.t1 for s in whole)
    cuts = [s for s in marks if s.tag in order]
    for a, b in zip(cuts, cuts[1:] + [None]):
        if b is not None and order.index(b.tag) == order.index(a.tag) + 1:
            parts[a.tag[5:]].append((b.t0 - a.t0) * 1e3)
        elif a.tag in order[2:]:  # the last cut: to the end of its span
            end = next((t for t in ends if t >= a.t0), None)
            if end is not None:
                parts[a.tag[5:]].append((end - a.t0) * 1e3)
    whole = [s.dur_ms for s in whole]
    print("probe: ck/resync whole ms", stats(whole))
    for k, v in parts.items():
        print(f"probe: resync {k:6s}->next ms", stats(v))
    for kind in ("download", "download-chunk"):
        mine = [s for s in spans if s.kind == kind]
        lanes = sorted({s.lane for s in mine if s.lane is not None})
        for lane in lanes:
            issued = [s for s in mine if s.lane == lane
                      and s.tag == "part:issued"]
            landed = [s for s in mine if s.lane == lane
                      and s.tag == "part:landed"]
            ends = [s for s in mine if s.lane == lane and s.t1 > s.t0]
            land = [(b.t0 - a.t0) * 1e3 for a, b in zip(issued, landed)]
            copy = [(e.t1 - b.t0) * 1e3 for b, e in zip(landed, ends)]
            wait = [(b.t0 - e.t0) * 1e3 for b, e in zip(landed, ends)]
            print(f"probe: {kind} lane {lane}: issued->landed {stats(land)}"
                  f" | wait in finish {stats(wait)} | landed->copied "
                  f"{stats(copy)}")
    by_kind: dict = {}
    for s in spans:
        if s.t1 > s.t0:
            by_kind.setdefault(s.kind, []).append(s.dur_ms)
    n = max(len(walls), 1)
    print("probe: span kinds, total ms a call: " + " ".join(
        f"{k}={sum(v) / n:.3f}({len(v)})" for k, v in sorted(by_kind.items())))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
