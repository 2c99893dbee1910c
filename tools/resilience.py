#!/usr/bin/env python
"""Resilience scenario runner: a standalone CLI over
:func:`resilience_section` (ISSUE 13).

Three seeded scenarios, all exactness-checked (recovery that corrupts
results is not recovery):

- **drain-and-readmit** — a 2-lane enqueue workload with an injected
  lane stall (``utils/faultinject.py``, fixed seed): the lane's fence
  walls degrade, the HealthMonitor flips its verdict, and the
  DrainController quarantines it at a barrier — ``drain_recover_ms``
  is the wall from arming the fault to the drain taking effect (the
  share at 0, work re-dispatched onto the surviving lane).  The
  injection then clears and the scenario runs until the lane is
  re-admitted through probation hysteresis — no human intervention,
  no flapping, and the final image is bit-exact for every iteration
  the workload ran.

- **kill-and-rejoin** — an immediate-mode workload checkpoints each
  window through ``cluster/elastic.py`` (atomic tmp+rename), is killed
  mid-run (the cruncher discarded, plus a deliberately TORN newest
  checkpoint dir to exercise the corrupt-step fallback), and resumes
  on a NEW cruncher — with a different lane count when the rig has
  one, so the membership change records replayable
  ``member-leave``/``member-join`` re-splits.  ``rejoin_converge_iters``
  is how many post-resume windows the balancer needs to settle its
  split; the final image must equal the undisturbed run's closed form
  bit-identically (windows applied exactly once).

- **mixed-kind drain** (ISSUE 20) — a heterogeneous fleet (two fast
  accelerator-kind lanes + one slow host-CPU lane, kinds/priors
  emulated on CPU-only rigs) with the CPU lane stalled: the slow lane
  quarantines without dragging the fast lanes below their rate-implied
  floor, and the availability floor never engages (two fast lanes stay
  active throughout).

Usage::

    python tools/resilience.py [--stall-ms 250] [--windows 8] [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # standalone `python tools/resilience.py`
    sys.path.insert(0, REPO)

INC_SRC = """
__kernel void inc(__global float* a) {
    int i = get_global_id(0);
    a[i] = a[i] + 1.0f;
}
"""

N_ITEMS = 1024
LOCAL_RANGE = 64


def _mk_cruncher(devs, lanes: int):
    from cekirdekler_tpu.core import NumberCruncher

    return NumberCruncher(devs.subset(lanes), INC_SRC)


def drain_readmit_scenario(devices=None, stall_ms: float = 400.0,
                           max_windows: int = 48) -> dict:
    """One seeded drain-and-readmit run (see module docstring)."""
    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.hardware import platforms
    from cekirdekler_tpu.obs.drain import DrainController
    from cekirdekler_tpu.obs.health import HealthMonitor
    from cekirdekler_tpu.utils.faultinject import FAULTS

    devs = devices if devices is not None else platforms().cpus()
    if len(devs) < 2:
        return {"skipped": "needs >= 2 lanes"}
    cr = _mk_cruncher(devs, 2)
    cores = cr.cores
    # tight detector/controller windows: the scenario's job is to show
    # the LOOP closing, not to wait out production-scale hysteresis.
    # threshold 4.0 (vs the production 3.0): a contended CPU container's
    # natural fence-wall noise can brush 3x for a window or two, and a
    # spurious drain of the HEALTHY lane would trip the availability
    # floor and block the real one — the injected stall (default
    # 400 ms vs ~50-100 ms walls) clears 4x with margin either way
    cores.health = HealthMonitor(threshold=4.0, window=2,
                                 min_history=2, confirm=2)
    cores.drain = DrainController(
        cores.health, lanes=2, hold_barriers=1, confirm_clear=1)
    # pin the split: the scenario proves the DRAIN actuator, and the
    # drain mask redistributes shares independently of the balancer.
    # Left adaptive, every balancer re-split resets upload coverage and
    # makes window costs bimodal (sub-ms steady vs tens-of-ms re-upload
    # windows) — with the detector's deliberately tight 2-sample
    # windows, the healthy lane's baseline can land in the fast regime
    # and spuriously flag, tripping the availability floor (the
    # balancer's own behavior is covered by its own tests)
    cores.fixed_compute_powers = [0.5, 0.5]
    x = ClArray(np.zeros(N_ITEMS, np.float32), name="x")
    x.partial_read = True
    cr.enqueue_mode = True
    iters = 0

    def window():
        nonlocal iters
        x.compute(cr, 1, "inc", N_ITEMS, LOCAL_RANGE)
        iters += 1
        cr.barrier()

    out: dict = {"stall_ms": stall_ms}
    try:
        for _ in range(8):  # baseline windows
            window()
        FAULTS.arm(f"seed=42;lane-stall@lane1:delay_ms={stall_ms}")
        t0 = time.perf_counter()
        drained_at = None
        for i in range(max_windows):
            window()
            if cores.drain.lane_state(1) != "active":
                drained_at = i + 1
                break
        out["drain_recover_ms"] = (
            round((time.perf_counter() - t0) * 1000.0, 3)
            if drained_at is not None else None)
        out["windows_to_drain"] = drained_at
        if drained_at is not None:
            window()  # the mask takes effect on the next call
            out["ranges_after_drain"] = cores.ranges_of(1)
        FAULTS.disarm()
        readmit_at = None
        for i in range(max_windows):
            window()
            if cores.drain.lane_state(1) == "active":
                readmit_at = i + 1
                break
        out["windows_to_readmit"] = readmit_at
        cr.enqueue_mode = False  # flush
        image = np.asarray(x)
        out["iters"] = iters
        out["exact"] = bool(np.all(image == float(iters)))
        out["drain_report"] = cores.drain.report()
    finally:
        FAULTS.disarm()
        cr.dispose()
    return out


def mixed_drain_scenario(devices=None, stall_ms: float = 400.0,
                         max_windows: int = 48, skew: float = 8.0) -> dict:
    """Degradation containment on a HETEROGENEOUS fleet (ISSUE 20): two
    fast accelerator-kind lanes + one slow host-CPU lane in one Cores,
    the CPU lane stalled.  The drain must quarantine the slow lane at a
    barrier WITHOUT dragging the fast lanes below their rate-implied
    floor — a degraded 1x lane forfeits its own share, it never costs
    the 8x lanes theirs (the shares are pinned at the rate-implied
    split, so the floor is exact: post-drain fast ranges can only GROW
    as they absorb the quarantined share).  The availability floor
    never engages here (two fast lanes stay active), and the final
    image must be bit-exact for every iteration the workload ran."""
    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.balance import prior_split
    from cekirdekler_tpu.hardware import platforms
    from cekirdekler_tpu.obs.drain import DrainController
    from cekirdekler_tpu.obs.health import HealthMonitor
    from cekirdekler_tpu.utils.faultinject import FAULTS

    devs = devices if devices is not None else platforms().cpus()
    if len(devs) < 3:
        return {"skipped": "needs >= 3 lanes"}
    cr = _mk_cruncher(devs, 3)
    cores = cr.cores
    # the emulation seam (tools/hetero_sweep.py): a real mixed rig gets
    # these from jax.Device.device_kind via hardware.rate_prior
    cores.lane_kinds = ["tpu-emu", "tpu-emu", "cpu"]
    cores.rate_priors = [float(skew), float(skew), 1.0]
    priors = list(cores.rate_priors)
    total = sum(priors)
    # pin the split AT the rate-implied share (same detector-noise
    # rationale as drain_readmit_scenario; the live prior-seeded
    # balancer is covered by hetero_sweep + tests/test_hetero.py) —
    # with the pin, "rate-implied floor" is an exact per-lane number
    cores.fixed_compute_powers = [p / total for p in priors]
    floor = prior_split(N_ITEMS, LOCAL_RANGE, priors)
    cores.health = HealthMonitor(threshold=4.0, window=2,
                                 min_history=2, confirm=2)
    cores.drain = DrainController(
        cores.health, lanes=3, hold_barriers=1, confirm_clear=1)
    x = ClArray(np.zeros(N_ITEMS, np.float32), name="x")
    x.partial_read = True
    cr.enqueue_mode = True
    iters = 0
    slow = 2  # the host-CPU lane's index

    def window():
        nonlocal iters
        x.compute(cr, 1, "inc", N_ITEMS, LOCAL_RANGE)
        iters += 1
        cr.barrier()

    out: dict = {"stall_ms": stall_ms, "lane_kinds": list(cores.lane_kinds),
                 "rate_priors": priors, "rate_implied_floor": floor}
    try:
        for _ in range(8):  # baseline windows at the rate-implied split
            window()
        out["ranges_before"] = cores.ranges_of(1)
        FAULTS.arm(f"seed=42;lane-stall@lane{slow}:delay_ms={stall_ms}")
        drained_at = None
        for i in range(max_windows):
            window()
            if cores.drain.lane_state(slow) != "active":
                drained_at = i + 1
                break
        out["windows_to_drain"] = drained_at
        if drained_at is not None:
            window()  # the mask takes effect on the next call
            ranges = cores.ranges_of(1)
            out["ranges_after_drain"] = ranges
            out["slow_lane_drained"] = ranges[slow] == 0
            # the containment claim: the fast lanes never dip below the
            # rate-implied floor — they absorb the freed share instead
            out["fast_floor_ok"] = all(
                ranges[i] >= floor[i] for i in range(3) if i != slow)
            # the fast lanes were never touched by the quarantine
            out["fast_lanes_active"] = all(
                cores.drain.lane_state(i) == "active"
                for i in range(3) if i != slow)
        FAULTS.disarm()
        readmit_at = None
        for i in range(max_windows):
            window()
            if cores.drain.lane_state(slow) == "active":
                readmit_at = i + 1
                break
        out["windows_to_readmit"] = readmit_at
        cr.enqueue_mode = False  # flush
        image = np.asarray(x)
        out["iters"] = iters
        out["exact"] = bool(
            np.all(image == float(iters))
            and out.get("slow_lane_drained")
            and out.get("fast_floor_ok")
            and out.get("fast_lanes_active"))
    finally:
        FAULTS.disarm()
        cr.dispose()
    return out


def rejoin_scenario(devices=None, windows: int = 8, kill_after: int = 4,
                    ckpt_root: str | None = None) -> dict:
    """One kill-and-rejoin run (see module docstring)."""
    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.cluster.elastic import (
        Membership, resume_window, save_window)
    from cekirdekler_tpu.hardware import platforms

    devs = devices if devices is not None else platforms().cpus()
    if len(devs) < 2:
        return {"skipped": "needs >= 2 lanes"}
    root = ckpt_root or tempfile.mkdtemp(prefix="ck_rejoin_")
    own_root = ckpt_root is None
    out: dict = {"windows": windows, "kill_after": kill_after}
    lanes_a = 2
    lanes_b = 3 if len(devs) >= 3 else 2
    try:
        # ---- first incarnation: immediate-mode windows, one atomic
        # checkpoint per completed window (host arrays are current —
        # immediate mode writes back per call)
        cr = _mk_cruncher(devs, lanes_a)
        x = ClArray(np.zeros(N_ITEMS, np.float32), name="x")
        x.partial_read = True
        for w in range(1, kill_after + 1):
            x.compute(cr, 1, "inc", N_ITEMS, LOCAL_RANGE)
            save_window(root, w, {"x": np.asarray(x)},
                        member_steps=[LOCAL_RANGE] * lanes_a)
        cr.dispose()  # ---- the preemption: the incarnation dies here
        # a TORN newest step (a crashed writer's half-copied dir): the
        # resume must fall back to the last COMPLETE window
        torn = os.path.join(root, f"step_{kill_after + 1:012d}")
        os.makedirs(torn, exist_ok=True)
        with open(os.path.join(torn, "arrays.npz"), "wb") as f:
            f.write(b"not a zip")
        # ---- second incarnation: resume, reconcile membership, finish
        state = resume_window(root)
        out["resumed_window"] = state["window"]
        out["fell_back"] = state["window"] == kill_after
        m = Membership()
        m.establish({
            f"p{i}": s for i, s in enumerate(state["member_steps"])})
        transitions = m.sync(
            {f"p{i}": LOCAL_RANGE for i in range(lanes_b)}, total=N_ITEMS)
        out["membership_transitions"] = len(transitions)
        out["membership_epoch"] = m.epoch
        cr2 = _mk_cruncher(devs, lanes_b)
        x2 = ClArray(np.ascontiguousarray(state["arrays"]["x"]), name="x")
        x2.partial_read = True
        last_change = 0
        prev_ranges = None
        for i, w in enumerate(range(state["window"] + 1, windows + 1),
                              start=1):
            x2.compute(cr2, 1, "inc", N_ITEMS, LOCAL_RANGE)
            r = cr2.ranges_of(1)
            if prev_ranges is not None and r != prev_ranges:
                last_change = i
            prev_ranges = r
            save_window(root, w, {"x": np.asarray(x2)},
                        member_steps=[LOCAL_RANGE] * lanes_b)
        cr2.dispose()
        out["rejoin_converge_iters"] = max(1, last_change)
        image = np.asarray(x2)
        # the undisturbed run's closed form: every window applied
        # exactly once — bit-identical or the recovery lost/duplicated
        # a window update
        out["exact"] = bool(np.all(image == float(windows)))
        out["lanes"] = {"before": lanes_a, "after": lanes_b}
    finally:
        if own_root:
            shutil.rmtree(root, ignore_errors=True)
    return out


def resilience_section(devices=None, stall_ms: float = 400.0,
                       windows: int = 8) -> dict:
    """Both scenarios, headline floats hoisted to the top level
    (``drain_recover_ms``, ``rejoin_converge_iters``)."""
    drain = drain_readmit_scenario(devices, stall_ms=stall_ms)
    rejoin = rejoin_scenario(devices, windows=windows)
    mixed = mixed_drain_scenario(devices, stall_ms=stall_ms)
    exact = (bool(drain.get("exact")) and bool(rejoin.get("exact"))
             and (bool(mixed.get("exact")) or "skipped" in mixed))
    return {
        "drain_recover_ms": drain.get("drain_recover_ms"),
        "rejoin_converge_iters": rejoin.get("rejoin_converge_iters"),
        "readmit_windows": drain.get("windows_to_readmit"),
        "mixed_fast_floor_ok": mixed.get("fast_floor_ok"),
        "exact": exact,
        "drain": drain,
        "rejoin": rejoin,
        "mixed_drain": mixed,
    }


_COUNT_FLAG = "--xla_force_host_platform_device_count"


def _ensure_lanes() -> None:
    """Standalone-CLI lane guarantee: a stock machine's CPU platform
    exposes ONE device, which would skip both scenarios and make a
    pure environment gap read like a recovery failure.  Force the
    8-virtual-device host platform (tests/conftest.py's rig) unless
    the caller already pinned a count — harmless on accelerator rigs
    (the flag only shapes the HOST platform).  Must run before the
    first jax import (the scenarios import lazily)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if _COUNT_FLAG not in flags:
        os.environ["XLA_FLAGS"] = (flags + f" {_COUNT_FLAG}=8").strip()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python tools/resilience.py",
        description="seeded drain-and-readmit + kill-and-rejoin scenarios "
                    "(docs/RESILIENCE.md)")
    ap.add_argument("--stall-ms", type=float, default=400.0)
    ap.add_argument("--windows", type=int, default=8)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    _ensure_lanes()
    out = resilience_section(stall_ms=args.stall_ms, windows=args.windows)
    if args.json:
        print(json.dumps(out, indent=1, sort_keys=True, default=str,
                         allow_nan=False))
    else:
        print(f"drain_recover_ms      = {out['drain_recover_ms']}")
        print(f"rejoin_converge_iters = {out['rejoin_converge_iters']}")
        print(f"readmit_windows       = {out['readmit_windows']}")
        print(f"mixed_fast_floor_ok   = {out['mixed_fast_floor_ok']}")
        print(f"exact                 = {out['exact']}")
    skipped = [k for k in ("drain", "rejoin") if out[k].get("skipped")]
    if out["mixed_drain"].get("skipped"):
        # the mixed-kind scenario degrades to a note, not an exit-2: the
        # two homogeneous scenarios already ran on this rig
        print(f"note: mixed_drain skipped "
              f"({out['mixed_drain']['skipped']})")
    if skipped:
        # an environment gap is NOT a recovery failure — name it and
        # exit distinctly (2) so a gate never confuses the two
        print(f"skipped: {', '.join(skipped)} "
              f"({out[skipped[0]]['skipped']})")
        return 2
    return 0 if out["exact"] else 1


if __name__ == "__main__":
    sys.exit(main())
