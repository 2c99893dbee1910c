"""Streamed-transfer chunk sweep CLI: chunk counts × array sizes through
the chunked double-buffered partition-transfer path, with the transfer
autotuner's chosen point printed against the sweep optimum (the
measurement behind ISSUE 5's streamed transfers; methodology in
``workloads.overlap_chunk_sweep``).

Run on the target chip from the repo root:

    python tools/overlap_sweep.py [--ns 1048576,4194304]
                                  [--chunks 1,2,4,8,16,32]
                                  [--reps 3] [--iters 400] [--json]

Per size: the wall at each PINNED chunk count (chunks=1 is the
monolithic path — the identity baseline), the measured optimum, and the
autotuner's choice after the sweep's observations taught it this rig's
link.  ``choice_vs_optimum`` ~1.0 means the online model lands on the
measured best point; the candidate grid's discreteness and host-clock
noise make ~1.1 normal.  ``--json`` prints the raw artifact.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


_JSONSAFE = None


def _json_safe(o):
    """Delegates to tools/_jsonsafe.py (loaded by file path — this tool
    must run standalone, via `python tools/<name>.py`, AND as an
    importlib-loaded module with no package context)."""
    global _JSONSAFE
    if _JSONSAFE is None:
        import importlib.util

        p = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "_jsonsafe.py")
        spec = importlib.util.spec_from_file_location("ck_tools_jsonsafe", p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _JSONSAFE = mod.json_safe
    return _JSONSAFE(o)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ns", default="1048576,4194304",
                    help="comma-separated array lengths (f32 elements)")
    ap.add_argument("--chunks", default="1,2,4,8,16,32",
                    help="comma-separated pinned chunk counts")
    ap.add_argument("--reps", type=int, default=3,
                    help="timed runs per point (median kept)")
    ap.add_argument("--iters", type=int, default=400,
                    help="per-element heavy-kernel iterations (0 = plain "
                         "add, transfer-bound)")
    ap.add_argument("--local", type=int, default=256, help="local range")
    ap.add_argument("--json", action="store_true",
                    help="print the raw JSON artifact only")
    args = ap.parse_args()

    from cekirdekler_tpu.workloads import overlap_chunk_sweep

    try:
        out = overlap_chunk_sweep(
            ns=tuple(int(v) for v in args.ns.split(",")),
            chunk_counts=tuple(int(v) for v in args.chunks.split(",")),
            local_range=args.local,
            reps=args.reps,
            heavy_iters=args.iters,
        )
    except ValueError as e:
        ap.error(str(e))
    if args.json:
        print(json.dumps(_json_safe(out), allow_nan=False))
        return
    print(out["note"])
    for sz in out["sizes"]:
        print(f"\nn={sz['n']} ({sz['mib']} MiB moved/run)")
        print(f"{'chunks':>8} {'wall ms':>10}")
        for r in sz["rows"]:
            mark = " <- sweep optimum" if (
                r["chunks"] == sz["sweep_best_chunks"]) else ""
            print(f"{r['chunks']:>8} {r['wall_ms']:>10.3f}{mark}")
        print(
            f"autotuner chose {sz['autotuner_chunks']} chunks "
            f"({sz['autotuner_ms']:.3f} ms) vs optimum "
            f"{sz['sweep_best_chunks']} ({sz['sweep_best_ms']:.3f} ms): "
            f"choice_vs_optimum = {sz['choice_vs_optimum']}"
        )


if __name__ == "__main__":
    main()
