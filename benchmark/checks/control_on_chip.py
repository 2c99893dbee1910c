#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, the two numbers every limit is
set from: the largest value each compared number takes over sound runs of the
program on many seeds, and the smallest the control gives (the reference in
bfloat16 in the program's place).  One process for all seeds, because set-up
is most of a run:

    python3 benchmark/checks/control_on_chip.py --workload nbody_8k_window \
        --seeds 12 --control-seeds 3 --seconds 3

Prints one line per seed and a summary, and exits non-zero if the control
passed on any seed or a sound run failed.  The benchmark's own runs never
run the control.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_300_000_001)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    import numpy as np

    import cells
    import run

    import jax

    import cekirdekler_tpu as ct

    cell = cells.load_cell(args.workload)
    tpus = [d for d in jax.devices() if d.platform == "tpu"]
    if len(tpus) < cell.chips:
        print(f"needs {cell.chips} TPU chip(s)", file=sys.stderr)
        return 3
    devices = ct.all_devices().tpus()
    sound: dict[str, list] = {}
    control: dict[str, list] = {}
    bad = 0
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        compared: list = []
        result = run.run_cell(cell, seed, args.seconds, False, devices,
                              compared_out=compared)
        for c in compared:
            sound.setdefault(c.name, []).append(c.value)
        bad += not result["correct"]
        print(f"seed {seed} program correct={result['correct']} calls="
              f"{result['attempted']} {[list(c) for c in compared]}",
              flush=True)
        if k < args.control_seeds:
            data, values = cell.ref.inputs(cell.cfg, cell.params,
                                           np.random.default_rng(seed))
            per_call = int(cell.params["iterations_per_call"])
            plan = (cell.ref.call_values(cell.cfg, cell.params, values)
                    if hasattr(cell.ref, "call_values") else {})
            observed = {"outputs": None, "ranges_log": [],
                        "values": plan.get("cycle", [values])[-1],
                        "iterations": 1 + per_call * (
                            int(cell.params["warmup_calls"])
                            + result["attempted"]),
                        "fresh": {"outputs": None,
                                  "values": plan.get("apart", values),
                                  "iterations": 1 + per_call}}
            compared = cell.ref.compare(cell.cfg, cell.params, data, values,
                                        observed, seed, precision="bfloat16")
            for c in compared:
                control.setdefault(c.name, []).append(c.value)
            passed = all(c.ok for c in compared)
            bad += passed
            print(f"seed {seed} control(bfloat16) correct={passed} "
                  f"{[list(c) for c in compared]}", flush=True)
    summary = {name: {"sound_max": max(v), "sound_min": min(v),
                      "control_min": min(control.get(name, [float('nan')])),
                      "seeds": len(v)}
               for name, v in sound.items()}
    print(json.dumps({"workload": args.workload, "summary": summary}))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
