#!/usr/bin/env python3
"""Two sets of runs of one cell with the same seeds, and the spread of every
end-to-end metric as the builder's contract defines it: the distance between
the first and third quartile (``statistics.quantiles(values, n=4)``) as a
share of the median, the wider of the two sets.  A bound is about five times
the widest spread over the cells, never under 1 %.

    python3 benchmark/checks/sets.py --workload nbody_8k_window --seconds 30 \
        --seeds 11 12 13 14 15 16 --out chiprun_out/sets_nbody_8k_window.json

Every run is a new process of the benchmark's own command; this parent never
touches jax, so the chip belongs to one process at a time.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_once(command, workload, seed, seconds, trace) -> dict:
    t0 = time.time()
    proc = subprocess.run(
        [*command, "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True, check=False)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if proc.returncode != 0 or not lines:
        sys.stderr.write(proc.stderr[-3000:])
        raise SystemExit(f"run failed: rc {proc.returncode}")
    for ln in lines:
        if ln.startswith(("[bench] check", "[bench] window")):
            print("   ", ln)
    result = json.loads(lines[-1])
    result["process_s"] = time.time() - t0
    return result


def spread(values) -> float:
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def spread_without_farthest(values) -> float:
    """The driver's measure for tightness: the spread of a set without the
    run farthest from its median, where leaving it out narrows the spread."""
    med = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - med))[:-1]
    return min(spread(values), spread(rest)) if len(rest) >= 2 else spread(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced-seed", type=int, default=None,
                    help="one more run with --trace 1 on this seed")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        command = json.load(f)["command"]
    sets, all_ok = [], True
    for k in range(args.sets):
        runs = []
        for seed in args.seeds:
            r = run_once(command, args.workload, seed, args.seconds, 0)
            all_ok &= r["correct"]
            print(f"set {k + 1} seed {seed} correct={r['correct']} " + " ".join(
                f"{n}={m['value']:.6g}" for n, m in r["metrics"].items())
                + f" mem={r['device']['memory_peak_bytes']} "
                  f"process_s={r['process_s']:.1f}", flush=True)
            runs.append(r)
        sets.append(runs)
    report = {"workload": args.workload, "seconds": args.seconds,
              "seeds": args.seeds, "metrics": {}, "sets": sets}
    for name in sets[0][0]["metrics"]:
        per_set = [[r["metrics"][name]["value"] for r in runs]
                   for runs in sets]
        # a set's first run compiled in a fresh checkout: its set-up is
        # recorded apart, as the driver does
        if name == "setup_s":
            per_set = [v[1:] for v in per_set]
        meds = [statistics.median(v) for v in per_set]
        spreads = [spread(v) for v in per_set]
        trimmed = [spread_without_farthest(v) for v in per_set]
        report["metrics"][name] = {
            "medians": meds, "spreads": spreads, "widest": max(spreads),
            "spreads_without_farthest": trimmed,
            "second_vs_first_median": meds[-1] / meds[0] - 1.0}
        print(f"{name}: medians {meds} spreads "
              f"{[round(s, 5) for s in spreads]} without each set's "
              f"farthest run {[round(s, 5) for s in trimmed]} second/first "
              f"{meds[-1] / meds[0] - 1.0:+.5f}")
    if args.traced_seed is not None:
        r = run_once(command, args.workload, args.traced_seed,
                     args.seconds, 1)
        all_ok &= r["correct"]
        report["traced"] = r
        print("traced:", json.dumps({k: r[k] for k in
                                     ("correct", "metrics", "device",
                                      "breakdown")}))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(report, f)
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
