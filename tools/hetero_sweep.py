#!/usr/bin/env python
"""Heterogeneous-lane sweep: {fast-only, slow-only, mixed} at equal
total range — a standalone CLI over :func:`hetero_section`
(ISSUE 20).

The paper's headline feature is treating N *unequal* devices as ONE
device for a single kernel.  This tool proves the TPU-native port of
that claim end to end: a mixed lane set (fast kind + slow kind in one
``Cores``) must beat the best homogeneous subset at equal total range,
with the split seeded from the device-kind rate priors
(``hardware.rate_prior`` → ``core/balance.prior_split``) and attributed
per lane kind in the trace report.

Four arms, all computing the SAME bandwidth-bound kernel over the same
total range (results must be bit-identical — the exactness gate):

- **fast_only** — the best homogeneous subset's fast half alone.
- **slow_only** — the slow kind alone.
- **mixed** — both kinds in one Cores, ``rate_priors`` seeding the
  first split at the rate-implied share.
- **mixed_prior_off** — same lanes, priors forced flat: the control
  that quantifies what the prior saved (the offline twin of ``ckreplay
  whatif --set rate_prior=off``).

Rate emulation on CPU-only containers: virtual host lanes share one
silicon, so a *measured* mixed-vs-homogeneous wall comparison measures
scheduler noise, not heterogeneity.  The sweep therefore pins the
comparison via skewed virtual-device rates: the slow lane is made
honestly slow TO THE MEASUREMENT PLANE with a seeded ``slow-link``
fault (transfers run ``skew``× slower, proportional to measured wall,
so the balancer holds the skewed split), and the headline walls come
from the rate MODEL applied to each arm's actual converged split:
``wall_model = max_i(range_i / rate_i)``.  That model is deterministic
— same split, same number.  Measured walls ride along for reference.
On a rig with real accelerators the same arms run un-emulated and the
measured walls are the result of record.

Headline (exactness-gated)::

    hetero_speedup_vs_best_homog = best_homog_wall / mixed_wall

Usage::

    python tools/hetero_sweep.py [--n 262144] [--iters 6] [--skew 8]
                                 [--spill PATH] [--json]

Exit codes: 0 ok, 1 inexact (digest mismatch), 2 environment gap
(fewer than 2 lanes).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # standalone `python tools/hetero_sweep.py`
    sys.path.insert(0, REPO)

_COUNT_FLAG = "--xla_force_host_platform_device_count"

#: Emulated device-kind labels for the CPU-only pinned path.  The slow
#: kind is the honest host kind; the fast kind is labeled as emulated
#: so no artifact can read a CPU container as real TPU silicon.
EMU_FAST_KIND = "tpu-emu"
EMU_SLOW_KIND = "cpu"

_CID = 8020  # the prior-on arms' compute id
#: The flat-prior control records under its OWN cid so a spilled log's
#: `ckreplay whatif --set rate_prior=off` chain over _CID is pure
#: prior-on evidence, not polluted by the control's equal-seeded moves.
_CID_PRIOR_OFF = 8021

AXPY_SRC = """
__kernel void axpy(__global float* a, __global float* b) {
    int i = get_global_id(0);
    b[i] = a[i] * 1.5f + b[i];
}
"""


def _ensure_lanes() -> None:
    """Standalone-CLI lane guarantee (tools/resilience.py's): force the
    8-virtual-device host platform unless the caller already pinned a
    count — harmless on accelerator rigs (the flag only shapes the
    HOST platform).  Must run before the first jax import."""
    flags = os.environ.get("XLA_FLAGS", "")
    if _COUNT_FLAG not in flags:
        os.environ["XLA_FLAGS"] = (flags + f" {_COUNT_FLAG}=8").strip()


def _digest(arr) -> str:
    import numpy as np

    return hashlib.sha256(
        np.ascontiguousarray(arr).tobytes()).hexdigest()[:16]


def _balance_moves(rows, cid: int) -> int:
    """How many recorded load-balance decisions for ``cid`` actually
    MOVED the split — the convergence-cost count the prior exists to
    shrink (a prior-seeded chain should move ~0-1 times; an
    equal-seeded chain under 8x skew re-shards for several)."""
    moves = 0
    for r in rows:
        if r.kind != "load-balance" or r.inputs.get("cid") != cid:
            continue
        if list(r.outputs.get("ranges", [])) != \
                list(r.inputs.get("ranges", [])):
            moves += 1
    return moves


def _run_arm(devs, kinds, priors, fault: str | None, n: int,
             local_range: int, iters: int, trace: bool = False,
             cid: int = _CID) -> dict:
    """One arm: build a cruncher over ``devs``, pin its lane kinds and
    rate priors (the emulation seam — on a real mixed rig both already
    hold the true values), run ``iters`` windows, return wall / final
    split / digest (+ the per-lane-kind trace rollup when asked)."""
    import numpy as np

    from cekirdekler_tpu import ClArray, trace as cktrace
    from cekirdekler_tpu.core import NumberCruncher
    from cekirdekler_tpu.obs.decisions import DECISIONS
    from cekirdekler_tpu.trace.attribution import window_report
    from cekirdekler_tpu.utils.faultinject import FAULTS

    a_host = np.ones(n, np.float32)
    b_host = np.zeros(n, np.float32)
    a = ClArray(a_host, name="ha", read_only=True)
    b = ClArray(b_host, name="hb", partial_read=True)
    cr = NumberCruncher(devs, AXPY_SRC)
    cores = cr.cores
    cores.lane_kinds = list(kinds)
    cores.rate_priors = [float(p) for p in priors]
    group = a.next_param(b)
    mark = DECISIONS.total_recorded
    if fault:
        FAULTS.arm(fault)
    rep = None
    try:
        ctx = cktrace.tracing() if trace else None
        tr = ctx.__enter__() if ctx else None
        t0 = time.perf_counter()
        try:
            for _ in range(iters):
                group.compute(cr, cid, "axpy", n, local_range)
            wall_s = time.perf_counter() - t0
        finally:
            t1 = time.perf_counter()
            if ctx:
                ctx.__exit__(None, None, None)
        if tr is not None:
            rep = window_report(
                tr.snapshot(), t0, t1,
                lane_kinds=dict(enumerate(cores.lane_kinds)))
        split = list(cores.ranges_of(cid))
        rows = [r for r in DECISIONS.snapshot()
                if r.seq >= mark]
    finally:
        if fault:
            FAULTS.disarm()
        cr.dispose()
    out = {
        "lanes": len(kinds),
        "kinds": list(kinds),
        "rate_priors": [float(p) for p in priors],
        "wall_s": round(wall_s, 4),
        "final_split": split,
        "balance_moves": _balance_moves(rows, cid),
        "digest": _digest(b_host),
        "value_ok": bool(np.all(b_host == np.float32(1.5) * iters)),
    }
    if rep is not None:
        out["per_lane_kind"] = {
            k: {"ms": round(v["ms"], 3), "count": v["count"],
                "lanes": sorted(v["lanes"])}
            for k, v in rep.per_lane_kind.items()
        }
    return out


def _model_wall(split, rates) -> float:
    """Pinned per-iteration wall under the virtual rate model: the
    slowest lane's items/rate.  Units are arbitrary (items per rate
    unit) — only ratios between arms are read."""
    return max(r / max(float(k), 1e-9) for r, k in zip(split, rates))


def hetero_section(devices=None, n: int = 262144, local_range: int = 256,
                   iters: int = 6, skew: float = 8.0,
                   spill: str | None = None) -> dict:
    """The four-arm sweep + the pinned
    model comparison + the per-lane-kind attribution rollup."""
    from cekirdekler_tpu.hardware import platforms, rate_prior
    from cekirdekler_tpu.obs.decisions import DECISIONS

    plats = platforms() if devices is None else None
    accels = plats.accelerators() if plats is not None else \
        devices.accelerators()
    cpus = plats.cpus() if plats is not None else devices.cpus()

    out: dict = {"skew": float(skew), "n": n, "iters": iters}
    if len(accels) >= 1 and len(cpus) >= 1:
        # real mixed rig: true kinds, true priors, measured walls are
        # the artifact of record (pinned_model False)
        fast = accels.subset(1)
        slow = cpus.subset(1)
        fast_kinds = [str(d.jax_device.device_kind) for d in fast]
        slow_kinds = [str(d.jax_device.device_kind) for d in slow]
        rates = [rate_prior(k) for k in fast_kinds + slow_kinds]
        fault = None
        out["pinned_model"] = False
    elif len(cpus) >= 2:
        # CPU-only container: 1 fast + 1 slow virtual lane, the slow
        # one made honestly slow to the measurement plane (seeded
        # slow-link), the comparison pinned via the rate model
        fast = cpus.subset(1)
        slow = cpus.subset(2)[1:2]
        fast_kinds = [EMU_FAST_KIND]
        slow_kinds = [EMU_SLOW_KIND]
        rates = [float(skew), 1.0]
        fault = f"seed=42;slow-link@lane{{i}}:factor={float(skew)}"
        out["pinned_model"] = True
    else:
        out["skipped"] = "needs >= 2 lanes (or 1 accelerator + 1 cpu)"
        return out

    mixed_devs = fast + slow
    mixed_kinds = fast_kinds + slow_kinds
    arms = {
        "fast_only": _run_arm(
            fast, fast_kinds, rates[:1], None, n, local_range, iters),
        "slow_only": _run_arm(
            slow, slow_kinds, rates[1:],
            fault.format(i=0) if fault else None,
            n, local_range, iters),
        "mixed": _run_arm(
            mixed_devs, mixed_kinds, rates,
            fault.format(i=1) if fault else None,
            n, local_range, iters, trace=True),
        "mixed_prior_off": _run_arm(
            mixed_devs, mixed_kinds, [1.0] * len(mixed_kinds),
            fault.format(i=1) if fault else None,
            n, local_range, iters, cid=_CID_PRIOR_OFF),
    }
    out["arms"] = arms

    digests = [arms[k]["digest"] for k in
               ("fast_only", "slow_only", "mixed", "mixed_prior_off")]
    exact = (len(set(digests)) == 1
             and all(a["value_ok"] for a in arms.values()))
    out["exact"] = bool(exact)

    if out["pinned_model"]:
        walls = {
            "fast_only": _model_wall(arms["fast_only"]["final_split"],
                                     rates[:1]),
            "slow_only": _model_wall(arms["slow_only"]["final_split"],
                                     rates[1:]),
            "mixed": _model_wall(arms["mixed"]["final_split"], rates),
        }
    else:
        walls = {k: arms[k]["wall_s"] for k in
                 ("fast_only", "slow_only", "mixed")}
    out["walls"] = {k: round(v, 4) for k, v in walls.items()}
    best_homog = min(walls["fast_only"], walls["slow_only"])
    out["best_homog_arm"] = ("fast_only"
                             if walls["fast_only"] <= walls["slow_only"]
                             else "slow_only")
    speedup = (round(best_homog / walls["mixed"], 3)
               if walls["mixed"] > 0 else None)
    # the headline: minted ONLY under the exactness gate — never a
    # number whose results differ
    out["hetero_speedup_vs_best_homog"] = speedup if exact else None

    # prior evidence: the mixed chain's re-shard count vs the flat-
    # prior control's (the in-run twin of `ckreplay whatif`)
    out["prior_on_moves"] = arms["mixed"]["balance_moves"]
    out["prior_off_moves"] = arms["mixed_prior_off"]["balance_moves"]
    # prior-seeded first split within one quantization step of the
    # rate-implied split (the ckmodel invariant, observed live)
    tot = sum(arms["mixed"]["final_split"])
    implied = [tot * r / sum(rates) for r in rates]
    first = prior_first_split(n, local_range, rates)
    out["prior_split_within_one_step"] = all(
        abs(f - i) <= local_range for f, i in zip(first, implied))
    out["per_lane_kind"] = arms["mixed"].get("per_lane_kind", {})
    if spill:
        out["spill_path"] = DECISIONS.save_jsonl(spill)
    return out


def prior_first_split(total: int, step: int, rates) -> list[int]:
    """The mixed arm's actual seed split (same function Cores uses)."""
    from cekirdekler_tpu.core.balance import prior_split

    return prior_split(total, step, [float(r) for r in rates])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python tools/hetero_sweep.py",
        description="heterogeneous-lane sweep: mixed vs best homogeneous "
                    "subset at equal total range (docs/PARALLELISM.md)")
    ap.add_argument("--n", type=int, default=262144)
    ap.add_argument("--local-range", type=int, default=256)
    ap.add_argument("--iters", type=int, default=6)
    ap.add_argument("--skew", type=float, default=8.0)
    ap.add_argument("--spill", default=None,
                    help="save the run's decision log (jsonl) here — "
                         "the `ckreplay verify` evidence file")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    _ensure_lanes()
    out = hetero_section(n=args.n, local_range=args.local_range,
                         iters=args.iters, skew=args.skew,
                         spill=args.spill)
    if args.json:
        print(json.dumps(out, indent=1, sort_keys=True, default=str,
                         allow_nan=False))
    else:
        if "skipped" in out:
            print(f"skipped: {out['skipped']}")
        else:
            print(f"hetero_speedup_vs_best_homog = "
                  f"{out['hetero_speedup_vs_best_homog']}")
            print(f"walls ({'model' if out['pinned_model'] else 'measured'})"
                  f" = {out['walls']}")
            print(f"mixed split            = "
                  f"{out['arms']['mixed']['final_split']}")
            print(f"prior moves on/off     = "
                  f"{out['prior_on_moves']}/{out['prior_off_moves']}")
            print(f"exact                  = {out['exact']}")
    if "skipped" in out:
        return 2
    return 0 if out["exact"] else 1


if __name__ == "__main__":
    sys.exit(main())
