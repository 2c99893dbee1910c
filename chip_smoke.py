#!/usr/bin/env python
"""chip_smoke.py — the quickest proof the system still starts on the chip.

Drives the main path once, through the entry points a user calls
(``all_devices().tpus()``, ``NumberCruncher``, ``ClArray.compute``, enqueue
windows, ``DevicePipeline``/``ClPipeline``, ``ServeFrontend.submit``,
``flash_attention``, ``__graft_entry__.entry``), at the sizes upstream and
this repo document for real use, and checks every result against the plain
host reference the repo already has.  One process, no children; data from
``--seed``.

Fails (non-zero exit, reason named) when: no TPU is found; a stage raises;
a result misses its tolerance; a Pallas kernel ran interpreted; a
kernel-language kernel the routing policy sends to Pallas ran on the XLA
lowering; a lane's buffers sit on a device other than its own chip.

Pallas kernels are called the way users call them — ``interpret`` left
out, so the lowering follows the lane (``ops/platform.py``) — and every such
row checks the lowering it got: on a TPU lane a Mosaic custom call must be
in it.  The rows ISSUE 21 names with a forced lowering say so in their name.

Each stage is a function of ``(devices, sizes)`` returning result rows —
``tests/test_chip_smoke.py`` calls them on the CPU rig at toy sizes, where
the same rule interprets the kernels.  The "no chip -> fail" rule lives in
:func:`main` only.

Last line of stdout on success::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

# ---------------------------------------------------------------------------
# sizes: FULL is what main() runs on the chip; the CPU-rig test passes its own
# ---------------------------------------------------------------------------

FULL = {
    "seed": 0,
    # stage 1 — upstream's mandelbrot demo frame and Tester.nBody's scale
    "mandel_wh": 2048, "mandel_max_iter": 256, "local_range": 256,
    "mandel_per_call": 4, "mandel_window": 32, "mandel_marker_window": 8,
    "nbody_n": 8192, "nbody_iters": 150, "nbody_window": 50,
    # the wave membrane split by range: a row of u1 crosses lanes each step
    "halo_wh": 1024, "halo_window": 20,
    # Rodinia's BFS at the suite's middle input (graph65536.txt's size)
    "bfs_nodes": 65536,
    # SHOC's Reduction at 2^20 elements (4 MB, between its two smallest classes)
    "reduce_elements": 1 << 20,
    # SHOC's MD on a 32^3 lattice (its middle class is 36 864 atoms), 128 neighbours
    "md_side": 32, "md_neighbours": 128,
    # stage 2 — 256 MiB per array: not a cache
    "stream_n": 1 << 26, "stream_tuner_runs": 3,
    # stage 3 — the examples/wave_equation.py stage
    "wave_pushes": 40,
    # stage 4 — 4 tenants x 2 signatures x 16 requests, 16 MiB arrays
    "serve_tenants": 4, "serve_sigs": 2, "serve_reqs": 16,
    "serve_n": 1 << 22, "serve_local": 256,
    # stage 5 — the flash bench shape and the tiled / dense-fallback lengths
    "flash_bhd": (2, 8, 64),
    "flash_T": (4096, 8192), "flash_tiled_T": 640, "flash_dense_T": 96,
    "flash_oneshot_T": 512, "qkv_T": 1024,
    "saxpy_n": 1 << 22, "backend_n": 1 << 20, "backend_nbody_n": 4096,
    # the n-body launch whose tile is fitted (bodies, passes of its loop)
    "backend_nbody_tiled": (32768, 32768),
    "affine_n": 4096,
    # HPCG's SpMV: the grid the smoke runs, and the grid and rung whose
    # compiled program it only counts (the cell's: PERF.md s.4)
    "spmv_side": 64, "spmv_count_side": 192, "spmv_count_chunk": 1 << 22,
    # stage 6
    "trace_iters": 8,
}


class SmokeFailure(Exception):
    """A check of the smoke did not hold (the message names it)."""


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _row(name: str, lowering: str, cold_s: float, run_s: float,
         max_err: float, **extra) -> dict:
    return {"name": name, "lowering": lowering, "cold_s": round(cold_s, 3),
            "run_s": round(run_s, 4), "max_err": float(max_err), **extra}


def _require_lane_edge(row: str, marks: list, launches: list) -> None:
    """ISSUE 52's marks of ONE lane's phase of one synchronous compute, on
    one clock: ``marks`` are ``(when, tag)`` of the lane's instants,
    ``launches`` ``(open, end)`` of its ``launch`` spans.  ``phase-start``
    <= ``phase-locked`` <= the first launch's open <= its ``part:call`` <=
    its ``part:handed`` <= the first ``part:issued`` <= the last
    ``part:landed`` <= ``phase-done``, and ONE ``part:call`` /
    ``part:handed`` pair inside every launch."""
    at = {tag: sorted(t for t, said in marks if said == tag)
          for tag in ("phase-start", "phase-locked", "part:call",
                      "part:handed", "part:issued", "part:landed",
                      "phase-done")}
    launches = sorted(launches)
    _require(launches and all(at.values())
             and len(at["part:call"]) == len(at["part:handed"])
             == len(launches),
             f"{row}: marks {({k: len(v) for k, v in at.items()})} for "
             f"{len(launches)} launches")
    chain = [at["phase-start"][0], at["phase-locked"][0], launches[0][0],
             at["part:call"][0], at["part:handed"][0], at["part:issued"][0],
             at["part:landed"][-1], at["phase-done"][0]]
    _require(chain == sorted(chain),
             f"{row}: the lane's marks out of order: {chain}")
    _require(all(a <= c <= h <= b for (a, b), c, h in zip(
        launches, at["part:call"], at["part:handed"])),
        f"{row}: a launch without its part:call / part:handed inside it")


def _lane_platforms(devices) -> list[str]:
    return [d.jax_device.platform for d in devices]


def _check_placement(cr) -> None:
    """Every lane's cached buffers live on that lane's own chip."""
    for w in cr.cores.workers:
        for buf in list(w._buffers.values()):
            _require(
                buf.devices() == {w.device},
                f"lane {w.index} buffer on {buf.devices()}, "
                f"not its own {w.device}")


def _check_routing(cr, kernel: str, devices, expect: str) -> str:
    """On TPU lanes every launcher rung of ``kernel`` must have been built
    with the lowering the routing policy promises; returns what ran."""
    seen: set = set()
    for plat in set(_lane_platforms(devices)):
        got = cr.cores.program.lowerings(kernel, plat)
        seen |= got
        if plat == "tpu":
            _require(
                got == {(expect, None)},
                f"kernel {kernel!r} on tpu lanes: expected every rung on "
                f"the {expect} lowering, got {sorted(map(str, got))}")
    return "+".join(sorted({lo for lo, _v in seen})) or "none"


def _mosaic_calls(jitted, *args) -> int:
    """Mosaic custom calls in a jitted function's lowering for the platform
    ``args`` live on — 0 means every Pallas kernel inside lowered to the
    interpreter (or there is none)."""
    return jitted.lower(*args).as_text().count("tpu_custom_call")


def _check_compiled(platform: str, n_calls: int, what: str) -> None:
    """A Pallas kernel dispatched to a TPU must have lowered to Mosaic."""
    if platform == "tpu":
        _require(n_calls > 0, f"{what}: Pallas kernel ran interpreted "
                              "(no Mosaic call in the lowering)")


def _launcher_mosaic_calls(cr, kernel: str, n_arrays: int, values, lr: int,
                           n: int) -> dict:
    """``{lane platform: Mosaic calls}`` in the lowering of ``kernel``'s
    largest ladder rung — the jitted launcher ``compute()`` dispatched and
    the fused ladder nests under ``lax.cond`` — lowered for a lane of each
    platform in the cruncher."""
    import jax
    from jax.sharding import SingleDeviceSharding

    chunk = lr << ((n // lr).bit_length() - 1)
    out = {}
    for w in cr.cores.workers:
        plat = w.device.platform
        if plat in out:
            continue
        fn, _info = cr.cores.program.launcher(kernel, chunk, lr, n, plat)
        arrays = tuple(
            jax.ShapeDtypeStruct((n,), np.float32,
                                 sharding=SingleDeviceSharding(w.device))
            for _ in range(n_arrays))
        out[plat] = fn.lower(0, arrays, tuple(values)).as_text().count(
            "tpu_custom_call")
    return out


# ---------------------------------------------------------------------------
# stage 1: compute() per call and in an enqueue window
# ---------------------------------------------------------------------------

def _marker_window(cr, call, out, want, iters: int, label: str) -> dict:
    """One enqueue window with fine-grained queue control on.  The marker
    thread holds each launch's output until it retires, so a lane must NOT
    donate those buffers to the next fused launch while markers are on
    (``Worker.fused_donate``) — and the window must still be exact."""
    out.host()[:] = -1.0
    cr.fine_grained_queue_control = True
    try:
        cr.enqueue_mode = True
        for _ in range(iters):
            call()
        cr.barrier()
        donate = [w.fused_donate for w in cr.cores.workers]
        cr.enqueue_mode = False  # flush
        deadline = time.time() + 10.0
        while cr.count_markers_remaining() and time.time() < deadline:
            time.sleep(0.01)
        reached, left = cr.count_markers_reached(), cr.count_markers_remaining()
    finally:
        if cr.enqueue_mode:
            cr.enqueue_mode = False
        cr.fine_grained_queue_control = False
    err = float(np.abs(out.host() - want).max())
    _require(not any(donate),
             f"mandelbrot[{label}]: a lane donates its fused buffers while "
             f"the marker thread holds them ({donate})")
    _require(err == 0.0,
             f"mandelbrot[{label}]: marker window differs from the host "
             f"by {err}")
    _require(reached > 0 and left == 0,
             f"mandelbrot[{label}]: markers reached {reached}, still in "
             f"flight {left} after the flush")
    return {"donate": donate, "reached": reached}


def _mandelbrot_through_compute(devices, sizes, source, label, want, cid,
                                markers: bool = False):
    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher

    wh = sizes["mandel_wh"]
    n, lr = wh * wh, sizes["local_range"]
    vals = (-2.0, -1.25, 2.5 / wh, 2.5 / wh, wh, sizes["mandel_max_iter"])
    cr = NumberCruncher(devices, source)
    out = ClArray(n, np.float32, name=f"mandel_{cid}", read=False,
                  write=True)
    try:
        call = lambda: out.compute(cr, cid, "mandelbrot", n, lr, values=vals)
        _, cold_s = _timed(call)
        lanes_first_ms = cr.benchmarks_of(cid)
        ranges_first = cr.ranges_of(cid)
        err_call = float(np.abs(out.host() - want).max())
        run_s = cold_s
        for _ in range(sizes["mandel_per_call"] - 1):
            _, run_s = _timed(call)
        lanes_steady_ms = cr.benchmarks_of(cid)
        # the enqueue window: fused dispatch is the default path
        out.host()[:] = -1.0
        w0 = cr.fused_stats["windows"]
        cr.enqueue_mode = True
        t0 = time.perf_counter()
        for _ in range(sizes["mandel_window"]):
            call()
        cr.barrier()
        window_s = time.perf_counter() - t0
        _check_placement(cr)
        donate = [w.fused_donate for w in cr.cores.workers]
        cr.enqueue_mode = False  # flush
        ranges_last = cr.ranges_of(cid)
        fused = cr.fused_stats
        err_win = float(np.abs(out.host() - want).max())
        lowering = _check_routing(
            cr, "mandelbrot", devices,
            "python" if not isinstance(source, str) else "pallas")
        mosaic = _launcher_mosaic_calls(cr, "mandelbrot", 1, vals, lr, n)
        for plat, calls in mosaic.items():
            _check_compiled(plat, calls, f"mandelbrot[{label}] on {plat}")
        _require(fused["windows"] > w0,
                 f"mandelbrot[{label}]: fused windows never engaged "
                 f"(disengaged: {fused['disengaged']})")
        _require(err_call == 0.0 and err_win == 0.0,
                 f"mandelbrot[{label}]: image differs from mandelbrot_host "
                 f"(per-call {err_call}, window {err_win})")
        _require(all(r > 0 for r in ranges_last) and sum(ranges_last) == n,
                 f"mandelbrot[{label}]: lane shares {ranges_last}")
        if len({d.jax_device for d in devices if d.is_tpu}) > 1:
            # real chips see the frame's row skew as real time differences
            # (virtual CPU lanes share cores, so there it is not asserted)
            _require(ranges_first != ranges_last,
                     f"mandelbrot[{label}]: the balancer never moved the "
                     f"ranges off {ranges_first}")
        extra = {}
        if markers:
            extra["marker_window"] = _marker_window(
                cr, call, out, want, sizes["mandel_marker_window"], label)
        return _row(
            f"mandelbrot[{label}] compute()", lowering, cold_s, run_s,
            max(err_call, err_win), window_s=round(window_s, 3),
            fused_windows=fused["windows"] - w0,
            fused_iters=fused["fused_iters"], donate=donate,
            mosaic_calls=mosaic,
            ranges_first=ranges_first, ranges_last=ranges_last,
            lane_first_call_s=[round(m / 1e3, 3) for m in lanes_first_ms],
            lane_steady_call_s=[round(m / 1e3, 4) for m in lanes_steady_ms],
            **extra)
    finally:
        cr.dispose()


def _wave_window_across_lanes(lanes, sizes) -> dict:
    """The wave step (``waveStep rotate``, one compute) split by range over
    the lanes: one synchronous step, then two enqueue windows with the
    balancer moving the ranges between them.  Every lane reads one row of
    ``u1`` its neighbour wrote the step before: exact against the float64
    scheme at every cell, the rows fetched lane to lane (``ck/halo`` spans,
    every one ``d2d``), no window fused, no whole-array resync."""
    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu import trace as cktrace
    from cekirdekler_tpu.core.cruncher import NumberCruncher

    ex = _wave_example()
    side, lr = sizes["halo_wh"], sizes["local_range"]
    per, cid = sizes["halo_window"], 7105
    rng = np.random.default_rng(sizes["seed"] + 5)
    f = rng.standard_normal((side, side)).astype(np.float32)
    f[0, :] = f[-1, :] = 0.0
    f[:, 0] = f[:, -1] = 0.0
    u0 = ClArray(f.reshape(-1).copy(), name="u0", partial_read=True)
    u1 = ClArray(f.reshape(-1).copy(), name="u1", partial_read=True)
    frame = ClArray(side * side, np.float32, name="frame", read=False)
    group = u0.next_param(u1, frame)
    cr = NumberCruncher(lanes, ex.WAVE_SRC)
    try:
        step = lambda: group.compute(cr, cid, "waveStep rotate", side * side,
                                     lr, values=(side, side, ex.C2))
        _, cold_s = _timed(step)
        with cktrace.tracing() as tr:
            cr.enqueue_mode = True
            t0 = time.perf_counter()
            for _ in range(2):
                for _ in range(per):
                    step()
                cr.barrier()
            _check_placement(cr)
            cr.enqueue_mode = False  # flush
            run_s = time.perf_counter() - t0
            spans = tr.snapshot()
        a = b = f.astype(np.float64)
        for _ in range(1 + 2 * per):
            c = np.zeros_like(b)
            c[1:-1, 1:-1] = (2.0 * b[1:-1, 1:-1] - a[1:-1, 1:-1] + ex.C2 * (
                b[1:-1, :-2] + b[1:-1, 2:] + b[:-2, 1:-1] + b[2:, 1:-1]
                - 4.0 * b[1:-1, 1:-1]))
            a, b = b, c
        err = max(float(np.abs(u1.host() - b.reshape(-1)).max()),
                  float(np.abs(u0.host() - a.reshape(-1)).max()))
        tol = 5e-6 * float(np.abs(b).max())
        _require(err <= tol, f"wave window over {len(lanes)} lanes: max err "
                             f"{err} > {tol:.3g}")
        halos = [s for s in spans if s.kind == "halo"]
        _require(len(halos) >= (2 * per - 1) * len(lanes),
                 f"{len(halos)} halo spans in {2 * per} computes")
        _require(all(s.tag == "d2d" for s in halos),
                 f"exchanges not device to device: {sorted({s.tag for s in halos})}")
        _require(not any(s.kind == "resync" and s.tag == "range-move"
                         for s in spans), "a range move resynced whole arrays")
        _require(cr.fused_stats["windows"] == 0
                 and cr.fused_stats["disengaged"].get("halo") == 2,
                 f"fused path: {cr.fused_stats}")
        # each of the windows' per-call computes marks its parts, each lane
        # the end of its phase (trace/spans.py, "Part marks")
        parts = [s.tag for s in spans if s.kind == "engage"
                 and (s.tag or "").startswith("part:") and s.lane is None]
        _require(parts == ["part:stage", "part:submit", "part:join",
                           "part:note"] * (2 * per),
                 f"part marks of {2 * per} computes: {parts[:8]} .. "
                 f"{len(parts)}")
        for tag in ("phase-start", "phase-locked", "phase-done"):
            said = sum(s.kind == "enqueue" and s.tag == tag for s in spans)
            _require(said == 2 * per * len(lanes), f"{said} {tag} instants")
        # (ISSUE 52) and every launch of a lane ONE part:call / part:handed
        pairs = [s.tag for s in spans if s.kind == "engage"
                 and s.lane is not None]
        launched = sum(s.kind == "launch" for s in spans)
        _require(sorted(pairs) == ["part:call"] * launched
                 + ["part:handed"] * launched,
                 f"{len(pairs)} launch marks for {launched} launches")
        # a row up or down is a runtime shift: Pallas vetoes it (and says
        # why), so waveStep runs the vectorized lowering on every lane
        routed = {lo for plat in set(_lane_platforms(lanes))
                  for lo, _veto in cr.cores.program.lowerings("waveStep", plat)}
        _require(routed == {"xla"}, f"waveStep lowered as {sorted(routed)}")
        return _row("wave compute() halo window", "xla", cold_s, run_s,
                    err, lanes=len(lanes), ranges=cr.ranges_of(cid),
                    halo_spans=len(halos))
    finally:
        cr.dispose()


def _ramp(computes: int, cap: int = 16) -> list[int]:
    """The dispatches of a fused window of ``computes`` deferred iterations:
    the eager sub-batch ramps x1 x2 x4 .. up to ``cap``, the residue last."""
    out, k = [], 1
    while computes >= k:
        out.append(k)
        computes -= k
        k = min(2 * k, cap)
    return out + ([computes] if computes else [])


def _nbody_windows_start_on_the_ladder(devices, sizes) -> dict:
    """Three enqueue windows of the n-body kernel on ONE lane (the
    benchmark's ``nbody_8k_window``): the second and the third repeat the
    window before them and start on the fused ladder.  The third runs under
    a profiler session: its first ``ck/enqueue`` span reads ``start=ladder``,
    its ``ck/fused`` tags are the ramp, every ``ck/launch`` in it is a fused
    dispatch (no per-call launch of the kernel), and the velocities are
    ``sha1``-equal to the same windows with ``fused_dispatch = False``."""
    import hashlib

    import jax
    from jax.profiler import ProfileData

    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.workloads import NBODY_SRC

    lane = devices.subset(1)
    n, lr, per = sizes["nbody_n"], sizes["local_range"], sizes["nbody_window"]
    pos = (np.random.default_rng(sizes["seed"] + 34).random(
        (3, n), dtype=np.float32) - 0.5) * 2.0
    root = tempfile.mkdtemp(prefix="ck_smoke_starts_")
    digests, timing, events = {}, {}, []
    try:
        for fused in (False, True):
            arrays = [ClArray(pos[i].copy(), name=c, read_only=True)
                      for i, c in enumerate("xyz")]
            vel = [ClArray(n, np.float32, name=f"v{c}", partial_read=True)
                   for c in "xyz"]
            group = arrays[0].next_param(*arrays[1:], *vel)
            cr = NumberCruncher(lane, NBODY_SRC)
            cr.fused_dispatch = fused

            def window():
                for _ in range(per):
                    group.compute(cr, 7106, "nBody", n, lr, values=(n, 1e-4))
                cr.barrier()

            try:
                cr.enqueue_mode = True
                _, cold = _timed(window)
                _, run = _timed(window)
                if fused:
                    timing = {"cold_s": cold, "run_s": run}
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(root, profiler_options=opts)
                    try:
                        window()
                    finally:
                        jax.profiler.stop_trace()
                    starts = dict(cr.fused_stats["window_starts"])
                else:
                    window()
                cr.enqueue_mode = False  # flush
                digests[fused] = [hashlib.sha1(v.host().tobytes()).hexdigest()
                                  for v in vel]
            finally:
                cr.dispose()
        path = [os.path.join(r, f) for r, _d, fs in os.walk(root)
                for f in fs if f.endswith(".xplane.pb")][0]
        for plane in ProfileData.from_file(path).planes:
            if plane.name == "/host:CPU":
                events += [(ev.start_ns, ev.name, dict(ev.stats))
                           for line in plane.lines for ev in line.events
                           if ev.name.startswith("ck/")]
    finally:
        shutil.rmtree(root, ignore_errors=True)
    events.sort(key=lambda e: e[0])
    enqueues = [st for _t, name, st in events if name == "ck/enqueue"]
    fused_tags = [str(st.get("tag")) for _t, name, st in events
                  if name == "ck/fused"]
    launches = [str(st.get("tag")) for _t, name, st in events
                if name == "ck/launch"]
    # a TPU lane's launches name the Pallas tiles of their ladder's rungs
    # (PR 36), the whole range's among them; a host lane takes the XLA
    # lowering and names none
    tiles = {str(st.get("tile")) for _t, name, st in events
             if name == "ck/launch"}
    rows_total = n // 128
    tile_rows = min(64, rows_total)
    want_tile = (f"{tile_rows}x128;grid={rows_total // tile_rows};live=6"
                 if lane[0].jax_device.platform == "tpu" else "None")
    # the barrier's part marks and the lane's retire anchor; a window of
    # deferred computes holds no other mark but its dispatches' own
    marks = [(name, str(st.get("tag")), st.get("lane"))
             for _t, name, st in events
             if str(st.get("tag")).startswith("part:")
             or st.get("tag") in ("retired", "phase-start", "phase-locked",
                                  "phase-done")]
    # (ISSUE 52) every fused dispatch marks where the runtime takes it and
    # where it has: ONE ``ck/engage`` pair a ``ck/launch``, with the lane
    pairs = [m for m in marks if m[0] == "ck/engage"]
    marks = [m for m in marks if m not in pairs]
    _require(starts == {"first-sighting": 1, "ladder": 2},
             f"window starts {starts}")
    _require(len(enqueues) == per and enqueues[0].get("start") == "ladder"
             and all(str(st.get("tag")).endswith("fused-defer")
                     for st in enqueues),
             f"third window's enqueue spans: {enqueues[:2]} of {len(enqueues)}")
    _require(fused_tags == [f"x{k}" for k in _ramp(per)],
             f"third window's fused dispatches {fused_tags}")
    _require(launches and all(t.startswith("fused:") for t in launches),
             f"a per-call launch in a window started on the ladder: {launches}")
    _require(all(want_tile in t.split("+") for t in tiles),
             f"ck/launch spans carry tile {tiles}, expected {want_tile}")
    _require(pairs == [("ck/engage", "part:call", 0),
                       ("ck/engage", "part:handed", 0)] * len(launches),
             f"third window's launch marks: {pairs} for {len(launches)} "
             "launches")
    _require(marks == [("ck/fence", "part:wait", None),
                       ("ck/fence", "retired", 0),
                       ("ck/fence", "part:close", None)],
             f"third window's marks: {marks}")
    _require(digests[True] == digests[False] and any(
        v.host().any() for v in vel),
        f"velocities differ from fused_dispatch=False: {digests}")
    return _row("nBody windows start on the ladder", "", timing["cold_s"],
                timing["run_s"], 0.0, window_starts=starts, ramp=fused_tags,
                sha1=digests[True][0][:16])


def _mandelbrot_frame_read_back(devices, sizes, want) -> dict:
    """The demo as its users run it (PR 38): ONE lane, a synchronous
    ``compute()`` a frame, the frame in the caller's array at every return.
    The third frame runs inside a profiler session: every download of it
    shows ``part:issued`` (the copy to the host is on its way) before
    ``part:landed`` (inside the download's span: the bytes are in host
    memory) before the span's end (the frame is in the caller's array), and
    the downloads' bytes add up to the frame's.  Every dispatch of the frame
    hands its run-time scalars over in one piece (ISSUE 39): four floats, two
    ints and the offset are seven words in one vector, none crosses alone."""
    import jax
    from jax.profiler import ProfileData

    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.workloads import MANDELBROT_SRC

    wh = sizes["mandel_wh"]
    n, lr = wh * wh, sizes["local_range"]
    vals = (-2.0, -1.25, 2.5 / wh, 2.5 / wh, wh, sizes["mandel_max_iter"])
    cr = NumberCruncher(devices.subset(1), MANDELBROT_SRC)
    out = ClArray(n, np.float32, name="mandel_shown", read=False, write=True)
    root = tempfile.mkdtemp(prefix="ck_smoke_readback_")
    events, launches, edge = [], [], []
    try:
        call = lambda: out.compute(cr, 7107, "mandelbrot", n, lr, values=vals)
        _, cold_s = _timed(call)
        call()  # the transfer tuner's first choice after its measuring run
        out.host()[:] = -1.0
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(root, profiler_options=opts)
        try:
            _, run_s = _timed(call)
        finally:
            jax.profiler.stop_trace()
        err = float(np.abs(out.host() - want).max())
        chunks = cr.cores.last_stream_chunks.get(0)
        path = [os.path.join(r, f) for r, _d, fs in os.walk(root)
                for f in fs if f.endswith(".xplane.pb")][0]
        for plane in ProfileData.from_file(path).planes:
            if plane.name == "/host:CPU":
                events += [(ev.start_ns, ev.start_ns + ev.duration_ns, li,
                            dict(ev.stats))
                           for li, line in enumerate(plane.lines)
                           for ev in line.events
                           if ev.name in ("ck/download", "ck/download-chunk")]
                launches += [dict(ev.stats, t0=ev.start_ns,
                                  t1=ev.start_ns + ev.duration_ns)
                             for line in plane.lines
                             for ev in line.events if ev.name == "ck/launch"]
                edge += [(ev.start_ns, dict(ev.stats))
                         for line in plane.lines for ev in line.events
                         if ev.name in ("ck/enqueue", "ck/engage")
                         and dict(ev.stats).get("lane") == 0]
    finally:
        cr.dispose()
        shutil.rmtree(root, ignore_errors=True)
    # a launch's tag ends in its dispatches: ``mandelbrot x2`` is two rungs
    scalars = [(str(s.get("tag")), str(s.get("scalars"))) for s in launches]
    _require(scalars and all(
        field == f"packed:{7 * int(t.rpartition(' x')[2])};loose:0"
        for t, field in scalars),
        f"the frame's launches did not pack their scalars: {scalars}")
    tag = lambda e: str(e[3].get("tag"))
    spans = sorted(e for e in events if not tag(e).startswith("part:"))
    issued = {e[3].get("off"): e for e in events if tag(e) == "part:issued"}
    landed = {e[3].get("off"): e for e in events if tag(e) == "part:landed"}
    _require(spans and len(spans) == len(issued) == len(landed),
             f"read-back marks: {len(spans)} downloads, {len(issued)} issued, "
             f"{len(landed)} landed")
    copy_ns = 0
    for off, mark in landed.items():
        inside = [s for s in spans if s[2] == mark[2]
                  and s[0] <= mark[0] and mark[1] <= s[1]]
        _require(len(inside) == 1 and off in issued
                 and issued[off][1] <= inside[0][0]
                 and issued[off][0] < mark[0] < inside[0][1],
                 f"read-back at offset {off}: issued {issued.get(off)}, "
                 f"landed {mark}, span {inside}")
        copy_ns += inside[0][1] - mark[0]
    # the lane's half of the compute (ISSUE 52), on the profile's clock:
    # the four new marks in order around the read-back's, and the pool hop
    # on ``phase-start`` under a key of its own
    _require_lane_edge(
        "mandelbrot frame read back",
        [(t, str(st.get("tag"))) for t, st in edge]
        + [(e[0], tag(e)) for e in events if tag(e).startswith("part:")],
        [(s["t0"], s["t1"]) for s in launches])
    starts = [st for _t, st in edge if st.get("tag") == "phase-start"]
    _require(len(starts) == 1 and float(starts[0].get("hop_us", -1.0)) >= 0.0
             and "queued_us" not in starts[0],
             f"phase-start without its hop: {starts}")
    nbytes = sum(int(e[3].get("bytes", 0)) for e in landed.values())
    _require(nbytes == 4 * n == sum(int(s[3].get("bytes", 0)) for s in spans),
             f"the frame's downloads carry {nbytes} bytes, the frame is "
             f"{4 * n}")
    _require(err == 0.0, f"the frame read back differs from the host by {err}")
    first = min(e[0] for e in issued.values())
    return _row("mandelbrot frame read back", "", cold_s, run_s, err,
                downloads=len(spans), bytes=nbytes, stream_chunks=chunks,
                launches=len(scalars), scalars=scalars[0][1],
                hop_us=float(starts[0]["hop_us"]),
                readback_ms=round((spans[-1][1] - first) / 1e6, 3),
                copy_ms=round(copy_ns / 1e6, 3))


def _bfs_traversal(devices, sizes) -> dict:
    """Rodinia's breadth-first search as its host loop drives it (PR 40): ONE
    lane, level by level a synchronous ``compute()`` of ``BFS_1 BFS_2`` and a
    look at the one-byte flag the kernels raise, the graph and the state
    resident in between, ``cost`` back at the end.  Exact against the
    configuration's plain reference; the two stores through ``edges[i]``
    built as scatters (an ``int`` and a ``char``) and the flag's store as
    one element; the flag crossed as ONE byte each way a level and nothing
    with ``read = false`` crossed at all."""
    import importlib.util

    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.core.worker import launch_ladder
    from cekirdekler_tpu.kernel.registry import lowering_meta

    configs = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmark", "configs")
    spec = importlib.util.spec_from_file_location(
        "rodinia_bfs_ref", os.path.join(configs, "rodinia_bfs_ref.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    with open(os.path.join(configs, "rodinia_bfs.cl")) as f:
        source = f.read()
    nodes, lr = sizes["bfs_nodes"], 256
    n = (nodes // lr + 1) * lr  # a range that is not the node count
    data, values = ref.inputs(
        {"nodes": nodes, "graph_seed": 1, "seed_relabels": True}, {"n": n},
        np.random.default_rng(sizes["seed"]))
    graph = ("starting", "no_of_edges", "edges")
    state = ("mask", "updating", "visited", "cost")
    arr = {k: ClArray(data[k], name=k) for k in graph + state + ("over",)}
    for k in graph:
        arr[k].read_only = True
    arr["over"].write_all = True
    group = arr["starting"].next_param(*list(arr.values())[1:])
    cr = NumberCruncher(devices.subset(1), source)
    w = cr.cores.workers[0]
    try:
        def traverse(base: int) -> int:
            start = int(data["relabel"][base])
            for k in state[:3]:
                arr[k].host()[:] = 0
            arr["cost"].host()[:] = -1
            arr["mask"][start] = arr["visited"][start] = 1
            arr["cost"][start] = 0
            for k in state:
                arr[k].read, arr[k].write = True, False
            arr["over"].read = arr["over"].write = True
            levels = 0
            while True:
                arr["over"][0] = 0
                group.compute(cr, 7108, "BFS_1 BFS_2", n, lr, values=values)
                levels += 1
                if levels == 1:
                    for k in graph + state:
                        arr[k].read = False
                if not arr["over"][0]:
                    break
            arr["over"].read = arr["over"].write = False
            arr["cost"].write = True
            cr.no_compute_mode = True
            try:
                group.compute(cr, 7108, "BFS_1 BFS_2", n, lr, values=values)
            finally:
                cr.no_compute_mode = False
            return levels

        _, cold_s = _timed(lambda: traverse(0))
        up0, down0 = w._m_whole_up.value, w._m_whole_down.value
        levels, run_s = _timed(lambda: traverse(1))
        up, down = w._m_whole_up.value - up0, w._m_whole_down.value - down0
        want, want_levels = ref.bfs(data["starting"], data["no_of_edges"],
                                    data["edges"], int(data["relabel"][1]))
        differing = int((arr["cost"].host() != want).sum())
        _require(differing == 0 and levels == want_levels,
                 f"BFS: {differing} nodes differ, {levels} levels for "
                 f"{want_levels}")
        # one byte up and one back a level; the state up once, cost back
        _require(up == 7 * n + levels and down == 4 * n + levels,
                 f"BFS moved {up} bytes up and {down} back whole over "
                 f"{levels} levels (state {7 * n}, cost {4 * n})")
        # the launchers of the ladder's first rung, as the traversal built them
        infos = [cr.cores.program.launcher(
            name, launch_ladder(n, lr)[0], lr, n,
            platform=w.device.platform)[1] for name in ("BFS_1", "BFS_2")]
        meta = lowering_meta(infos)
        _require("scatter:2" in meta["access"] and "uniform:1" in meta["access"]
                 and meta["scatter"] == "stores:2;width:4+1",
                 f"BFS: access {meta['access']}, scatter "
                 f"{meta.get('scatter')}")
        # a first rung wider than a chunk builds BFS_1's adjacency loop
        # compactable (PR 41): the reads at tid are the chunks' gathers, and
        # its entering lanes go to their chunks by trip count (PR 53)
        from cekirdekler_tpu.kernel import codegen

        width = codegen._COMPACT_WIDTH
        want_field = (f"loops:1;width:{width};gathered:3;scattered:0;ordered:1"
                      if launch_ladder(n, lr)[0] > width else None)
        _require(meta.get("compact") == want_field,
                 f"BFS: compact {meta.get('compact')}, expected {want_field}")
        # a level's wall (one synchronous compute: the device's time and a
        # byte each way) beside what the parent of PR 41 took at this size:
        # 10 levels in 0.141 s at 65 536 nodes (my chip run, PR 40)
        return _row("BFS traversal compute()", meta["lowering"], cold_s,
                    run_s, float(differing), levels=levels,
                    access=meta["access"], scatter=meta["scatter"],
                    compact=meta.get("compact", ""),
                    level_ms=round(1e3 * run_s / levels, 3),
                    level_ms_pr40_at_65536=14.1,
                    flag_bytes_up=up - 7 * n, flag_bytes_back=down - 4 * n)
    finally:
        cr.dispose()


def _group_reduction(devices, sizes) -> dict:
    """SHOC's ``reduce`` (PR 45): work items of a group cooperate through a
    ``__local`` tile and barriers.  ONE lane, 64 groups of 256, one
    synchronous ``compute()``; the 64 partials back in the caller's array
    equal the configuration's plain reference partial by partial, the host's
    sum is the array's, and the launch's span fields say how the tile was
    lowered: two barriers, six shifts, one broadcast, no row fallback, on
    the XLA half with the ``local-memory`` veto; and how the walk reads: both
    reads of ``g_idata`` one window a group, the windows settled once a
    launch, not a pass (``settled:2``), and the passes every lane is sure to
    make run with no mask (``loops`` ends in ``peeled:1``)."""
    import importlib.util

    from cekirdekler_tpu import ClArray, trace
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.kernel.registry import lowering_meta

    configs = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmark", "configs")
    spec = importlib.util.spec_from_file_location(
        "shoc_reduction_ref", os.path.join(configs, "shoc_reduction_ref.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    with open(os.path.join(configs, "shoc_reduction.cl")) as f:
        source = f.read()
    elements, groups, lr = sizes["reduce_elements"], 64, 256
    n = groups * lr
    data, _values = ref.inputs({"elements": elements, "local_range": lr},
                               {"n": n}, np.random.default_rng(sizes["seed"]))
    x = ClArray(data["g_idata"], name="g_idata", read_only=True)
    out = ClArray(data["g_odata"], name="g_odata", read=False, write=True,
                  write_all=True)
    group = x.next_param(out)
    cr = NumberCruncher(devices.subset(1), source)
    w = cr.cores.workers[0]
    try:
        def reduce_to(upto: int) -> float:
            group.compute(cr, 7109, "reduce", n, lr, values=(upto,))
            return float(np.sum(out.host(), dtype=np.float64))

        _, cold_s = _timed(lambda: reduce_to(elements))
        upto = elements - 2 * n  # another prefix: a stale partial would show
        # (the ring is on for this call: the lane's marks, below)
        with trace.tracing() as tr:
            total, run_s = _timed(lambda: reduce_to(upto))
        spans = tr.snapshot()
        _require_lane_edge(
            "group reduction compute()",
            [(s.t0, s.tag) for s in spans if s.lane == 0 and s.t0 == s.t1],
            [(s.t0, s.t1) for s in spans if s.kind == "launch"])
        want = ref.partials(data["g_idata"], upto, groups, lr)
        differing = int((out.host().astype(np.float64) != want).sum())
        _require(differing == 0 and total == float(want.sum()),
                 f"reduce: {differing} partials differ, sum {total} for "
                 f"{float(want.sum())}")
        info = cr.cores.program.launcher(
            "reduce", n, lr, n, platform=w.device.platform)[1]
        meta = lowering_meta([info])
        _require(meta.get("local") == "arrays:1;bytes:1024;barriers:2;"
                 "sites:shift:6,uniform:1,row:0"
                 and meta["access"].endswith(
                     ";gather:0;scatter:1;carried:0;local:7;group:2;settled:2")
                 and meta["loops"] == "counted:1;masked:1;peeled:1",
                 f"reduce: local {meta.get('local')}, access {meta['access']}, "
                 f"loops {meta['loops']}")
        if w.device.platform == "tpu":
            _require((info.lowering, (info.veto or "")[:12])
                     == ("xla", "local-memory"),
                     f"reduce: lowering {info.lowering}, veto {info.veto}")
        return _row("group reduction compute()", meta["lowering"], cold_s,
                    run_s, float(differing), local=meta["local"],
                    access=meta["access"], loops=meta["loops"],
                    call_ms=round(1e3 * run_s, 3), sum=total)
    finally:
        cr.dispose()


def _vector_kernel(devices, sizes) -> dict:
    """SHOC's ``compute_lj_force`` (PR 50): ``float4`` positions and forces as
    vector types of the kernel language, ``elements_per_work_item`` 4.  ONE
    lane, one synchronous ``compute()`` a frame of positions; every atom's
    force back in the caller's array equals the configuration's plain
    reference, ``w`` is the 0 the kernel stores, and the launch's span fields
    say what was built: one load, one gather (a row fetch a neighbour), one
    store for the two ``float4`` parameters, no scatter, on the XLA half with
    the ``vector-types`` veto."""
    import importlib.util

    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.kernel.registry import lowering_meta

    configs = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmark", "configs")
    spec = importlib.util.spec_from_file_location(
        "shoc_md_ref", os.path.join(configs, "shoc_md_ref.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    with open(os.path.join(configs, "shoc_md.cl")) as f:
        source = f.read()
    side, k, lr = sizes["md_side"], sizes["md_neighbours"], 256
    n = side ** 3
    cfg = {"atoms": n, "lattice": [side] * 3, "neighbours": k, "cutsq": 16.0,
           "spacing": 0.4775, "jitter": 0.15, "displacement": 0.01}
    data, values = ref.inputs(cfg, {"n": n},
                              np.random.default_rng(sizes["seed"]))
    plan = ref.call_values(cfg, {"n": n}, values)
    force = ClArray(data["force3"], name="force3", read=False, write=True,
                    elements_per_work_item=4)
    position = ClArray(data["position"], name="position", read=True,
                       write=False, elements_per_work_item=4)
    neigh = ClArray(data["neighList"], name="neighList", read_only=True)
    group = force.next_param(position, neigh)
    cr = NumberCruncher(devices.subset(1), source)
    w = cr.cores.workers[0]
    try:
        def step(frame: int) -> None:
            position.host()[:] = data["frames"][frame]
            group.compute(cr, 7110, "compute_lj_force", n, lr,
                          values=tuple(plan["cycle"][frame]))

        _, cold_s = _timed(lambda: step(0))
        neigh.read = False  # the list crossed, and stays
        _, run_s = _timed(lambda: step(1))  # another frame, another lj pair
        _k, cutsq, lj1, lj2, _n = plan["cycle"][1]
        want = ref.forces(data["frames"][1].reshape(n, 4),
                          data["neighList"].reshape(k, n), np.arange(n),
                          cutsq, lj1, lj2)
        got = force.host().reshape(n, 4)
        err = float(np.abs(got[:, :3] - want).max() / np.abs(want).max())
        _require(err < 1e-5 and not got[:, 3].any(),
                 f"md: force error {err} of the largest, "
                 f"{int((got[:, 3] != 0).sum())} w not 0")
        info = cr.cores.program.launcher(
            "compute_lj_force", n, lr, n, platform=w.device.platform)[1]
        meta = lowering_meta([info])
        _require(meta.get("vector") == "params:2;width:4;loads:1;gathers:1;"
                 "stores:1" and ";gather:1;scatter:0;" in meta["access"]
                 and "scatter" not in meta,
                 f"md: vector {meta.get('vector')}, access {meta['access']}, "
                 f"scatter {meta.get('scatter')}")
        if w.device.platform == "tpu":
            _require((info.lowering, (info.veto or "")[:12])
                     == ("xla", "vector-types"),
                     f"md: lowering {info.lowering}, veto {info.veto}")
        return _row("vector kernel compute()", meta["lowering"], cold_s, run_s,
                    err, vector=meta["vector"], access=meta["access"],
                    loops=meta["loops"], call_ms=round(1e3 * run_s, 3))
    finally:
        cr.dispose()


def stage_compute(devices, sizes) -> list[dict]:
    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.workloads import (
        MANDELBROT_SRC, NBODY_SRC, mandelbrot_host, mandelbrot_pallas_kernel,
        nbody_host_step)

    wh = sizes["mandel_wh"]
    want = mandelbrot_host(wh, wh, -2.0, -1.25, 2.5 / wh, 2.5 / wh,
                           sizes["mandel_max_iter"])
    # the hand-tiled kernel twice: as users build it (the lowering follows
    # each lane), and with the lowering forced the way ISSUE 21 names it —
    # forced to what the lanes are, so the CPU rig can run the row too
    on_chip = all(p == "tpu" for p in _lane_platforms(devices))
    rows = [
        _mandelbrot_through_compute(
            devices, sizes, MANDELBROT_SRC, "kernel-language", want, 7101,
            markers=True),
        _mandelbrot_through_compute(
            devices, sizes, mandelbrot_pallas_kernel(), "hand Pallas", want,
            7102),
        _mandelbrot_through_compute(
            devices, sizes, mandelbrot_pallas_kernel(interpret=not on_chip),
            f"hand Pallas interpret={not on_chip}", want, 7104),
    ]

    # n-body: the benchmark cells' shape — balanced over >= 2 lanes
    # (two partition lanes of the chip when there is only one)
    lanes = devices if len(devices) > 1 else devices[0].as_partitions(2)
    n, lr, dt = sizes["nbody_n"], sizes["local_range"], 1e-4
    rng = np.random.default_rng(sizes["seed"])
    pos = (rng.random((3, n), dtype=np.float32) - 0.5) * 2.0
    x, y, z = (ClArray(pos[i].copy(), name=c, read_only=True)
               for i, c in enumerate("xyz"))
    vel = [ClArray(n, np.float32, name=f"v{c}", partial_read=True)
           for c in "xyz"]
    zero = np.zeros(n, np.float32)
    v1 = nbody_host_step(pos[0], pos[1], pos[2], zero, zero, zero, dt)
    cr = NumberCruncher(lanes, NBODY_SRC)
    group = x.next_param(y, z, *vel)
    cid = 7103
    try:
        step = lambda: group.compute(cr, cid, "nBody", n, lr, values=(n, dt))
        _, cold_s = _timed(step)  # synchronous first step: the +-0.01 check
        err1 = max(float(np.abs(g.host() - w).max())
                   for g, w in zip(vel, v1))
        _require(err1 <= 0.01, f"nBody first step: max err {err1} > 0.01")
        cr.enqueue_mode = True
        t0 = time.perf_counter()
        for k in range(sizes["nbody_iters"]):
            step()
            if (k + 1) % sizes["nbody_window"] == 0:
                cr.barrier()
        _check_placement(cr)
        cr.enqueue_mode = False  # flush
        run_s = time.perf_counter() - t0
        # positions are read-only, so every step adds the same v1: the
        # final velocities are (1 + iters) x the host step
        total = 1 + sizes["nbody_iters"]
        errN = max(float(np.abs(g.host() - total * w).max())
                   for g, w in zip(vel, v1))
        tol = 0.01 + 1e-5 * total * max(float(np.abs(w).max()) for w in v1)
        _require(errN <= tol,
                 f"nBody after {total} steps: max err {errN} > {tol:.4f}")
        ranges = cr.ranges_of(cid)
        _require(all(r > 0 for r in ranges), f"nBody lane shares {ranges}")
        lowering = _check_routing(cr, "nBody", lanes, "pallas")
        rows.append(_row(
            "nBody compute() balanced", lowering, cold_s, run_s, err1,
            err_final=errN, lanes=len(lanes), ranges=ranges,
            fused_windows=cr.fused_stats["windows"]))
    finally:
        cr.dispose()
    rows.append(_wave_window_across_lanes(lanes, sizes))
    rows.append(_nbody_windows_start_on_the_ladder(devices, sizes))
    rows.append(_mandelbrot_frame_read_back(devices, sizes, want))
    rows.append(_bfs_traversal(devices, sizes))
    rows.append(_group_reduction(devices, sizes))
    rows.append(_vector_kernel(devices, sizes))
    return rows


# ---------------------------------------------------------------------------
# stage 2: transfers at a size that is not a cache
# ---------------------------------------------------------------------------

# STREAM triad.  s = 2 makes s*b exact, so a fused multiply-add and a
# separate multiply + add round identically and numpy is a bit-exact oracle.
TRIAD_SRC = """
__kernel void triad(__global float* a, __global float* b, __global float* c,
                    float s) {
    int i = get_global_id(0);
    c[i] = a[i] + s * b[i];
}
"""


def stage_transfers(devices, sizes) -> list[dict]:
    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cores import PIPELINE_DRIVER, PIPELINE_EVENT
    from cekirdekler_tpu.core.cruncher import NumberCruncher

    n, lr, s = sizes["stream_n"], sizes["local_range"], 2.0
    rng = np.random.default_rng(sizes["seed"] + 1)
    a_h = rng.random(n, dtype=np.float32)
    b_h = rng.random(n, dtype=np.float32)
    want = a_h + np.float32(s) * b_h
    a = ClArray(a_h, name="a", partial_read=True, read_only=True)
    b = ClArray(b_h, name="b", partial_read=True, read_only=True)
    c = ClArray(n, np.float32, name="c", write_only=True)
    group = a.next_param(b, c)
    cr = NumberCruncher(devices, TRIAD_SRC)
    rows = []

    def run(label, cid, repeats=1, **kw):
        c.host()[:] = 0.0
        call = lambda: group.compute(cr, cid, "triad", n, lr, values=(s,),
                                     **kw)
        _, cold_s = _timed(call)
        run_s = cold_s
        for _ in range(repeats - 1):
            _, run_s = _timed(call)
        _require(np.array_equal(c.host(), want),
                 f"triad[{label}] differs from numpy")
        return cold_s, run_s

    try:
        cr.streamed_transfers = False
        cold_s, run_s = run("monolithic", 7201, repeats=2)
        rows.append(_row("triad monolithic", "", cold_s, run_s, 0.0))
        cr.streamed_transfers = True  # the default
        # the tuner's first contact is a fenced measuring run; the later
        # calls are where it is free to pick its chunk count
        cold_s, run_s = run("streamed", 7202,
                            repeats=sizes["stream_tuner_runs"])
        chunks = dict(cr.cores.last_stream_chunks)
        rows.append(_row("triad streamed", "", cold_s, run_s, 0.0,
                         tuner_chunks=chunks))
        cold_s, run_s = run("EVENT x8", 7203, pipeline=True,
                            pipeline_blobs=8, pipeline_type=PIPELINE_EVENT)
        rows.append(_row("triad pipeline EVENT x8", "", cold_s, run_s, 0.0))
        cold_s, run_s = run("DRIVER x16", 7204, pipeline=True,
                            pipeline_blobs=16, pipeline_type=PIPELINE_DRIVER)
        rows.append(_row("triad pipeline DRIVER x16", "", cold_s, run_s, 0.0))
        _check_placement(cr)
        lowering = _check_routing(cr, "triad", devices, "pallas")
        for r in rows:
            r["lowering"] = lowering
    finally:
        cr.dispose()
    return rows


# ---------------------------------------------------------------------------
# stage 3: stage pipeline
# ---------------------------------------------------------------------------

def _wave_example():
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "examples", "wave_equation.py")
    spec = importlib.util.spec_from_file_location("ck_wave_example", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# one more kernel for the one-stage-per-chip chain: hand (u1, frame) on as
# the next stage's (u0, u1) through TRANSITION arrays
_FORWARD_SRC = """
__kernel void forward(__global float* u0, __global float* u1,
                      __global float* frame,
                      __global float* n0, __global float* n1,
                      int width, int height, float c2) {
    int i = get_global_id(0);
    n0[i] = u1[i];
    n1[i] = frame[i];
}
"""


def stage_pipeline(devices, sizes) -> list[dict]:
    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.pipeline.device_pipeline import (
        ClPipeline, DevicePipeline, PipelineStage)

    ex = _wave_example()
    W, H = ex.W, ex.H
    yy, xx = np.mgrid[0:H, 0:W]
    bump = np.exp(-(((xx - W // 3) ** 2) / 18.0
                    + ((yy - H // 2) ** 2) / 18.0))
    u1_init = (0.6 * bump).reshape(-1).astype(np.float32)
    u0_init = u1_init.copy()
    vals = (W, H, ex.C2)
    rows = []

    # the example's own stage: state device-resident, live readback per push
    stage = PipelineStage(ex.WAVE_SRC, "waveStep rotate", global_range=W * H,
                          local_range=ex.LOCAL, values=vals)
    stage.add_hidden(ClArray(u0_init.copy(), name="u0"))
    stage.add_hidden(ClArray(u1_init.copy(), name="u1"))
    stage.add_output(ClArray(W * H, np.float32, name="frame"))
    pipe = DevicePipeline.make([stage], devices[0])
    out = np.zeros(W * H, np.float32)
    try:
        _, cold_s = _timed(lambda: pipe.push(None, out))
        t0 = time.perf_counter()
        for _ in range(sizes["wave_pushes"] - 1):
            pipe.push(None, out)
        run_s = time.perf_counter() - t0
        for s in stage._slots():
            _require(s.value.devices() == {devices[0].jax_device},
                     f"pipeline slot {s.arr.name} on {s.value.devices()}")
        plat = devices[0].jax_device.platform
        routed = {k: sorted(stage.program.lowerings(k, plat), key=str)
                  for k in ("waveStep", "rotate")}
        lowering = "+".join(sorted(
            {lo for got in routed.values() for lo, _v in got}))
        # waveStep's shifts are runtime values (u1[i - width]): outside the
        # tile subset, so the XLA lowering is the POLICY, with its reason
        vetoes = {k: v for k, got in routed.items() for _lo, v in got if v}
    finally:
        pipe.dispose()
    err = float(np.abs(
        out - ex.host_reference(u0_init, u1_init, sizes["wave_pushes"])
    ).max())
    _require(err < 1e-3, f"wave DevicePipeline: max err {err} >= 1e-3")
    rows.append(_row("wave DevicePipeline", lowering, cold_s, run_s, err,
                     pushes=sizes["wave_pushes"], vetoes=vetoes))

    if len(devices) > 1:
        # one stage per chip, each advancing the field one step; the same
        # initial state is fed every push, so once the chain is full the
        # last stage emits K steps of it — host_reference(.., K)
        k = len(devices)
        stages = []
        ins = (ClArray(W * H, np.float32, name="p0_u0"),
               ClArray(W * H, np.float32, name="p0_u1"))
        for i in range(k):
            last = i == k - 1
            st = PipelineStage(
                ex.WAVE_SRC + _FORWARD_SRC,
                "waveStep" if last else "waveStep forward",
                global_range=W * H, local_range=ex.LOCAL, values=vals)
            st.add_input(*ins)
            if last:
                st.add_output(ClArray(W * H, np.float32, name=f"p{i}_frame"))
            else:
                st.add_hidden(ClArray(W * H, np.float32, name=f"p{i}_frame"))
                ins = (ClArray(W * H, np.float32, name=f"p{i + 1}_u0"),
                       ClArray(W * H, np.float32, name=f"p{i + 1}_u1"))
                st.add_transition(*ins)
            stages.append(st)
        pipe = ClPipeline.make(stages, list(devices))
        out = np.zeros(W * H, np.float32)
        try:
            _, cold_s = _timed(
                lambda: pipe.push([u0_init, u1_init], out))
            t0 = time.perf_counter()
            for _ in range(k + 2):
                valid = pipe.push([u0_init, u1_init], out)
            run_s = time.perf_counter() - t0
            _require(valid, "ClPipeline never reported valid results")
            for st, d in zip(stages, devices):
                for s in st._slots():
                    _require(s.value.devices() == {d.jax_device},
                             f"ClPipeline slot {s.arr.name} on "
                             f"{s.value.devices()}, not {d.jax_device}")
        finally:
            pipe.dispose()
        err = float(np.abs(
            out - ex.host_reference(u0_init, u1_init, k)).max())
        _require(err < 1e-3, f"wave ClPipeline x{k}: max err {err} >= 1e-3")
        rows.append(_row(f"wave ClPipeline x{k} chips", lowering, cold_s,
                         run_s, err))
    return rows


# ---------------------------------------------------------------------------
# stage 4: serving
# ---------------------------------------------------------------------------

def stage_serving(devices, sizes) -> list[dict]:
    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.serve import ServeFrontend, ServeJob
    from tools.loadgen import LOADGEN_SRC

    n, lr = sizes["serve_n"], sizes["serve_local"]
    tenants, sigs, reqs = (sizes["serve_tenants"], sizes["serve_sigs"],
                           sizes["serve_reqs"])
    cr = NumberCruncher(devices, LOADGEN_SRC)
    arrays, jobs = [], []
    for s in range(sigs):
        arr = ClArray(np.zeros(n, np.float32), name=f"serve{s}")
        arr.partial_read = True
        arrays.append(arr)
        jobs.append(ServeJob(params=[arr], kernels=["lg_inc"],
                             compute_id=7400 + s, global_range=n,
                             local_range=lr))
    fe = ServeFrontend(cr, name="chip-smoke")
    results: list = []
    errors: list = []
    mu = threading.Lock()

    def client(tenant: str, job) -> None:
        try:
            futs = [fe.submit(tenant, job) for _ in range(reqs)]
            got = [f.result(timeout=300.0) for f in futs]
            with mu:
                results.extend(got)
        except Exception as e:  # noqa: BLE001 - reported by the stage
            with mu:
                errors.append(f"{tenant}: {type(e).__name__}: {e}")

    try:
        warm, warm_s = _timed(lambda: fe.warmup(jobs))  # set-up
        w0 = cr.fused_stats["windows"]
        i0 = cr.fused_stats["fused_iters"]
        threads = [
            threading.Thread(target=client, args=(f"tenant{t}", jobs[s]))
            for t in range(tenants) for s in range(sigs)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600.0)
        run_s = time.perf_counter() - t0
        _require(not any(t.is_alive() for t in threads),
                 "serving: client threads still running")
        _require(not errors, f"serving: {errors[:3]}")
        fe.close()
        total = tenants * sigs * reqs
        _require(len(results) == total,
                 f"serving: {len(results)} of {total} futures resolved")
        per_sig = float(tenants * reqs)
        err = max(float(np.abs(np.asarray(arr) - per_sig).max())
                  for arr in arrays)
        _require(err == 0.0,
                 f"serving: arrays differ from {per_sig} by up to {err}")
        windows = cr.fused_stats["windows"] - w0
        per_call = total - (cr.fused_stats["fused_iters"] - i0)
        launches = windows + per_call
        _require(0 < launches < total,
                 f"serving: {launches} launches for {total} requests — "
                 "coalescing never engaged")
        _check_placement(cr)
        lowering = _check_routing(cr, "lg_inc", devices, "pallas")
        return [_row(f"ServeFrontend {tenants}x{sigs}x{reqs}", lowering,
                     warm_s, run_s, err, requests=total, launches=launches,
                     coalesce_ratio=round(total / launches, 2),
                     warmup=warm.get("warmed"))]
    finally:
        if not fe._halt:
            fe.close(drain=False)
        cr.dispose()


# ---------------------------------------------------------------------------
# stage 5: every Pallas kernel the repo ships compiles under Mosaic
# ---------------------------------------------------------------------------

def _rel(a, b) -> float:
    import jax.numpy as jnp

    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))


def _dense_grads(q, k, v, heads_per_chunk: int = 2):
    """Gradients of ``_dense_attention(..).sum()`` — the reference — taken
    per (batch, head-chunk) so the [T, T] scores of the long shapes fit."""
    import jax
    import jax.numpy as jnp

    from cekirdekler_tpu.ops.flash_attention import _dense_attention

    g = jax.jit(jax.grad(
        lambda q, k, v: _dense_attention(q, k, v, True, "highest").sum(),
        argnums=(0, 1, 2)))
    B, _T, H, _D = q.shape
    hc = min(heads_per_chunk, H)
    outs = [[], [], []]
    for b in range(B):
        parts = [g(*(a[b:b + 1, :, h:h + hc] for a in (q, k, v)))
                 for h in range(0, H, hc)]
        for i in range(3):
            outs[i].append(jnp.concatenate([p[i] for p in parts], axis=2))
    return tuple(jnp.concatenate(o, axis=0) for o in outs)


def stage_kernels(devices, sizes) -> list[dict]:
    import jax
    import jax.numpy as jnp

    from cekirdekler_tpu.kernel import codegen, lang
    from cekirdekler_tpu.kernel.pallas_backend import build_kernel_fn_pallas
    from cekirdekler_tpu.ops.elementwise import saxpy
    from cekirdekler_tpu.ops.flash_attention import (
        _dense_attention, flash_attention, fused_qkv, fused_qkv_attention)
    from cekirdekler_tpu.workloads import (
        MANDELBROT_SRC, NBODY_SRC, WAVE_SRC, mandelbrot_host,
        nbody_host_step)

    dev = devices[0].jax_device
    plat = dev.platform
    # hand kernels are called with `interpret` left out: the lowering
    # follows the device, and each row states (and checks) what it got
    pallas_ran = "mosaic" if plat == "tpu" else "interpret"
    rng = np.random.default_rng(sizes["seed"] + 2)
    B, H, D = sizes["flash_bhd"]
    rows = []

    def qkv(T):
        return tuple(
            jax.device_put(
                (rng.standard_normal((B, T, H, D)) * 0.3).astype(np.float32),
                dev)
            for _ in range(3))

    def first_and_repeat(fn, *args):
        out, cold_s = _timed(lambda: jax.block_until_ready(fn(*args)))
        _, run_s = _timed(lambda: jax.block_until_ready(fn(*args)))
        return out, cold_s, run_s

    # flash fwd+bwd, default arguments (the BlockTuner path), both precisions
    for T in sizes["flash_T"]:
        q, k, v = qkv(T)
        want = _dense_grads(q, k, v)
        for precision, tol in (("highest", 5e-4), ("default", 2e-2)):
            loss = lambda q, k, v: flash_attention(
                q, k, v, causal=True, precision=precision).sum()
            g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            got, cold_s, run_s = first_and_repeat(g, q, k, v)
            n_calls = _mosaic_calls(g, q, k, v)
            _check_compiled(plat, n_calls, f"flash T={T} {precision}")
            err = max(_rel(a, b) for a, b in zip(got, want))
            _require(
                err < tol and all(bool(jnp.isfinite(a).all()) for a in got),
                f"flash grads T={T} {precision}: rel err {err} >= {tol}")
            rows.append(_row(f"flash fwd+bwd T={T} {precision}", pallas_ran,
                             cold_s, run_s, err, mosaic_calls=n_calls))
        del want

    # T=640 stays tiled (128-wide blocks); T=96 takes the dense fallback
    for T, tiled in ((sizes["flash_tiled_T"], True),
                     (sizes["flash_dense_T"], False)):
        q, k, v = qkv(T)
        f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
        got, cold_s, run_s = first_and_repeat(f, q, k, v)
        n_calls = _mosaic_calls(f, q, k, v)
        if tiled:
            _check_compiled(plat, n_calls, f"flash T={T}")
        else:
            _require(n_calls == 0, f"flash T={T} did not take dense")
        err = _rel(got, _dense_attention(q, k, v, True, "highest"))
        _require(err < 5e-4, f"flash fwd T={T}: rel err {err}")
        rows.append(_row(f"flash fwd T={T}",
                         pallas_ran if tiled else "dense",
                         cold_s, run_s, err))

    # fused QKV projection + tuned flash; the one-shot softmax (block_k == T)
    T, E = sizes["qkv_T"], H * D
    x = jax.device_put(
        (rng.standard_normal((B, T, E)) * 0.3).astype(np.float32), dev)
    wq, wk, wv = (jax.device_put(
        (rng.standard_normal((E, E)) / np.sqrt(E)).astype(np.float32), dev)
        for _ in range(3))
    f = jax.jit(lambda x, wq, wk, wv: fused_qkv_attention(
        x, wq, wk, wv, H, causal=True))
    got, cold_s, run_s = first_and_repeat(f, x, wq, wk, wv)
    _check_compiled(plat, _mosaic_calls(f, x, wq, wk, wv), "fused_qkv")
    q, k, v = (a.reshape(B, T, H, D) for a in fused_qkv(x, wq, wk, wv))
    err = _rel(got, _dense_attention(q, k, v, True, "highest"))
    _require(err < 2e-3, f"fused_qkv_attention: rel err {err}")
    rows.append(_row("fused_qkv_attention", pallas_ran, cold_s, run_s, err))
    T = sizes["flash_oneshot_T"]
    q, k, v = qkv(T)
    f = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=False, block_q=128, block_k=T))
    got, cold_s, run_s = first_and_repeat(f, q, k, v)
    _check_compiled(plat, _mosaic_calls(f, q, k, v), "one-shot softmax")
    err = _rel(got, _dense_attention(q, k, v, False, "highest"))
    _require(err < 5e-4, f"flash one-shot softmax: rel err {err}")
    rows.append(_row("flash one-shot softmax", pallas_ran, cold_s, run_s,
                     err))

    # ops/elementwise.saxpy
    n = sizes["saxpy_n"]
    xs = jax.device_put(rng.random(n, dtype=np.float32), dev)
    ys = jax.device_put(rng.random(n, dtype=np.float32), dev)
    f = jax.jit(lambda x, y: saxpy(2.0, x, y))
    got, cold_s, run_s = first_and_repeat(f, xs, ys)
    _check_compiled(plat, _mosaic_calls(f, xs, ys), "saxpy")
    err = float(np.abs(np.asarray(got)
                       - (np.asarray(ys) + np.float32(2.0) * np.asarray(xs))
                       ).max())
    _require(err == 0.0, f"saxpy: max err {err}")
    rows.append(_row("ops.saxpy", pallas_ran, cold_s, run_s, err))

    # one kernel of each kernel/pallas_backend.py access class, built as
    # the registry builds them for a TPU lane (it compiles under Mosaic;
    # only a rig without a chip asks the builder to interpret), against the
    # XLA lowering of the same source
    interp = plat != "tpu"

    def backend(src, name, n, arrays, values, label, want=None, tol=0.0,
                counted=0, tile=None):
        kdef = {k.name: k for k in lang.parse_kernels(src)}[name]
        pl_fn, info = build_kernel_fn_pallas(kdef, n, 256, n,
                                             interpret=interp, force=True)
        # a uniform loop whose proof fails without a word would still be
        # right, and three times slower (PERF.md, PR 29)
        _require(info.loops_counted >= counted,
                 f"{label}: {info.loops_counted} counted loop(s), "
                 f"{info.loops_masked} masked; expected {counted} counted")
        # (rows, grid steps): a masked loop and a kernel without one take
        # the tallest tile; a counted loop's is fitted to the tiles it
        # keeps alive, or every pass spills them (PERF.md, PR 36)
        tallest = min(256, n // 128)
        tile = tile or (tallest, n // 128 // tallest)
        _require((info.tile_rows, info.tile_grid) == tile,
                 f"{label}: tile of {info.tile_rows} rows in "
                 f"{info.tile_grid} grid step(s), live {info.loop_live}; "
                 f"expected {tile}")
        arrays = tuple(jax.device_put(a, dev) for a in arrays)
        f = jax.jit(lambda *arrs: pl_fn(0, arrs, values))
        got, cold_s, run_s = first_and_repeat(f, *arrays)
        _check_compiled(plat, _mosaic_calls(f, *arrays), label)
        _require(info.lowering == "pallas",
                 f"{label}: delegated to {info.lowering} ({info.veto})")
        if want is None:
            xla_fn, _ = codegen.build_kernel_fn(kdef, n, 256, n)
            want = jax.jit(lambda *arrs: xla_fn(0, arrs, values))(*arrays)
        err = max(float(np.abs(np.asarray(g) - np.asarray(w)).max())
                  for g, w in zip(got, want))
        _require(err <= tol, f"{label}: max err {err} > {tol}")
        rows.append(_row(f"pallas_backend {label}", pallas_ran, cold_s,
                         run_s, err))

    n = sizes["backend_n"]
    wh = int(np.sqrt(n))
    mvals = (np.float32(-2.0), np.float32(-1.25), np.float32(2.5 / wh),
             np.float32(2.5 / wh), np.int32(wh), np.int32(64))
    # exact on the chip; the CPU backend contracts the orbit's multiply-adds
    # differently under the interpreter, moving a boundary pixel by one
    backend(MANDELBROT_SRC, "mandelbrot", wh * wh,
            (np.zeros(wh * wh, np.float32),), mvals, "elementwise",
            want=(mandelbrot_host(wh, wh, -2.0, -1.25, 2.5 / wh, 2.5 / wh,
                                  64),), tol=1.0 if interp else 0.0)
    wave = tuple((rng.standard_normal(n) * 0.5).astype(np.float32)
                 for _ in range(3))
    backend(WAVE_SRC, "wave", n, wave, (), "halo (pl.Element)", tol=1e-4)
    nb = sizes["backend_nbody_n"]
    pos = (rng.random((3, nb), dtype=np.float32) - 0.5) * 2.0
    zero = np.zeros(nb, np.float32)
    v1 = nbody_host_step(pos[0], pos[1], pos[2], zero, zero, zero, 1e-4)
    backend(NBODY_SRC, "nBody", nb, (*pos, zero, zero, zero),
            (np.int32(nb), np.float32(1e-4)), "SMEM uniform gather",
            want=(*pos, *v1), tol=0.01, counted=1)
    nb, passes = sizes["backend_nbody_tiled"]
    pos = (rng.random((3, nb), dtype=np.float32) - 0.5) * 2.0
    zero = np.zeros(nb, np.float32)
    backend(NBODY_SRC, "nBody", nb, (*pos, zero, zero, zero),
            (np.int32(passes), np.float32(1e-4)), "fitted tile (n-body)",
            tol=0.01, counted=1, tile=(64, 4))

    # affine accesses on the vectorized-XLA lowering (Pallas vetoes them):
    # PolyBench/GPU's MVT, a matrix walked by rows and by columns with the
    # sums in global memory, built as the registry builds it for this lane.
    # A walk that fell back to a per-lane gather or a scatter a pass would
    # still be right, and ten thousand times slower (PERF.md, PR 30)
    from cekirdekler_tpu.kernel.registry import KernelProgram, lowering_meta

    def kept_views(info, label: str) -> str:
        """After a second call over the same arrays: the launch took its
        views as arguments and built none (PERF.md, PR 31)."""
        _require(info.views_kept == len(info.views) and info.views_built == 0,
                 f"{label}: views kept:{info.views_kept};"
                 f"built:{info.views_built} of {info.views}")
        return lowering_meta((info,))["views"]

    n = sizes["affine_n"]
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmark", "configs", "polybench_mvt.cl")) as f:
        mvt = KernelProgram(f.read())
    a = rng.standard_normal(n * n, dtype=np.float32)
    y1, y2 = (rng.standard_normal(n, dtype=np.float32) for _ in range(2))
    a64 = a.reshape(n, n).astype(np.float64)
    arrays = tuple(jax.device_put(h, dev) for h in (
        a, np.zeros(n, np.float32), np.zeros(n, np.float32), y1, y2))
    for name, at, want in (("mvt_kernel1", 1, a64 @ y1),
                           ("mvt_kernel2", 2, a64.T @ y2)):
        fn, info = mvt.launcher(name, n, 256, n, platform=plat)
        got, cold_s, run_s = first_and_repeat(
            lambda *arrs: fn(0, arrs, (n,)), *arrays)
        _require(info.access["gather"] == 0 and info.access["scatter"] == 0
                 and info.access["carried"] == 1 and info.keyed == {"n": n},
                 f"{name}: access {info.access}, keys {info.keyed}")
        err = float(np.abs(np.asarray(got[at]) - want).max()
                    / np.abs(want).max())
        _require(err < 1e-5, f"{name}: rel err {err}")
        # the row walk reads the kept 2-D view of the matrix
        _require(len(info.views) == (name == "mvt_kernel1"),
                 f"{name}: asks for {info.views}")
        rows.append(_row(f"affine {name}", info.lowering, cold_s, run_s, err,
                         access=";".join(f"{k}:{v}"
                                         for k, v in info.access.items()),
                         views=kept_views(info, name)))
    del arrays, a, a64

    # kept views on per-lane reads: HPCG's SpMV (run windows over ``col``
    # and ``val``, row gathers of ``x``), as the registry builds it for this
    # lane, against the grid's own product
    import importlib.util

    configs = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmark", "configs")
    spec = importlib.util.spec_from_file_location(
        "hpcg_spmv_ref", os.path.join(configs, "hpcg_spmv_ref.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    with open(os.path.join(configs, "hpcg_spmv.cl")) as f:
        spmv = KernelProgram(f.read())
    side = sizes["spmv_side"]
    cfg = {"nx": side, "ny": side, "nz": side, "alpha_cycle": [2.0]}
    n = side ** 3
    host, _values = ref.inputs(cfg, {"n": n}, rng)
    arrays = tuple(jax.device_put(host[k], dev)
                   for k in ("rowptr", "col", "val", "x", "y"))
    fn, info = spmv.launcher("spmv", n, 256, n, platform=plat)
    got, cold_s, run_s = first_and_repeat(
        lambda *arrs: fn(0, arrs, (np.float32(2.0),)), *arrays)
    want = ref.product(cfg, host["x"], 2.0)
    err = float(np.abs(np.asarray(got[4]) - want).max() / np.abs(want).max())
    _require(err < 1e-5, f"spmv: rel err {err}")
    _require(len(info.views) >= 2, f"spmv: asks for {info.views}")
    views = kept_views(info, "spmv")
    # the compiler's count for the cell's largest rung, views as arguments
    # against views built in the launch (the program of every launch before
    # PR 31): what is held BETWEEN launches grows, a launch must not
    side, chunk = sizes["spmv_count_side"], sizes["spmv_count_chunk"]
    n, nnz = side ** 3, (3 * side - 2) ** 3
    shapes = tuple(jax.ShapeDtypeStruct((k,), t) for k, t in (
        (n + 1, jnp.int32), (nnz, jnp.int32), (nnz, jnp.float32),
        (n, jnp.float32), (n, jnp.float32)))
    alpha = (jax.ShapeDtypeStruct((), jnp.float32),)
    big, _ = spmv.launcher("spmv", chunk, 256, n, platform=plat)
    count = {}
    for form, handed in (("kept", big.wants(shapes, alpha, None)),
                         ("in_launch", ())):
        mem = big.trace(
            jax.ShapeDtypeStruct((), jnp.int32), shapes, alpha, None,
            {s: jax.eval_shape(s.build, shapes[s.param]) for s in handed},
        ).lower().compile().memory_analysis()
        count[form] = {"arguments": int(mem.argument_size_in_bytes),
                       "temporaries": int(mem.temp_size_in_bytes)}
    _require(sum(count["kept"].values()) <= sum(count["in_launch"].values()),
             f"spmv: a launch's arguments + temporaries grew: {count}")
    rows.append(_row("views spmv", info.lowering, cold_s, run_s, err,
                     views=views, launch_bytes=count))

    # the driver's own entry point (beside this script)
    import __graft_entry__ as graft

    fn, args = graft.entry()
    args = jax.device_put(args, dev)
    got, cold_s, run_s = first_and_repeat(jax.jit(fn), *args)
    _require(bool(jnp.isfinite(got).all()), "entry(): non-finite logits")
    with jax.default_matmul_precision("highest"):
        ref = fn(*args)
    err = _rel(got, ref)
    _require(err < 5e-2, f"entry(): jit vs eager-highest rel err {err}")
    rows.append(_row("jit(__graft_entry__.entry)", "xla", cold_s, run_s, err,
                     shape=list(got.shape)))
    return rows


# ---------------------------------------------------------------------------
# stage 6: device trace
# ---------------------------------------------------------------------------

def stage_trace(devices, sizes) -> list[dict]:
    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.trace.device import DeviceCapture
    from cekirdekler_tpu.utils import timeline
    from cekirdekler_tpu.workloads import MANDELBROT_SRC

    wh, lr = sizes["mandel_wh"], sizes["local_range"]
    n = wh * wh
    vals = (-2.0, -1.25, 2.5 / wh, 2.5 / wh, wh, sizes["mandel_max_iter"])
    cr = NumberCruncher(devices, MANDELBROT_SRC)
    out = ClArray(n, np.float32, name="trace_out", read=False, write=True)
    root = tempfile.mkdtemp(prefix="ck_smoke_trace_")

    def window():
        for _ in range(sizes["trace_iters"]):
            out.compute(cr, 7601, "mandelbrot", n, lr, values=vals)
        cr.barrier()

    try:
        cr.enqueue_mode = True
        _, cold_s = _timed(window)  # compiled and fused outside the traces
        with timeline.capture(os.path.join(root, "timeline")) as result:
            window()
        tl = result()
        cap = DeviceCapture(os.path.join(root, "device"))
        with cap:
            _, run_s = _timed(window)
        cr.enqueue_mode = False
        rep = cap.report
        expect_device_events = all(
            p == "tpu" for p in _lane_platforms(devices))
        if expect_device_events:
            chips = len({d.jax_device for d in devices})
            _require(tl.n_events > 0,
                     "timeline.capture parsed zero device events "
                     f"(dump: {tl.trace_path})")
            _require(tl.n_devices >= chips,
                     f"timeline: {tl.n_devices} device planes < {chips}")
            _require(rep.absent is None, f"DeviceCapture: {rep.absent}")
            _require(rep.n_ops > 0 and rep.n_marks > 0,
                     f"DeviceCapture: {rep.n_ops} ops, {rep.n_marks} marks")
            _require(rep.attributed_ms > 0,
                     "DeviceCapture: no device op correlated to a launch "
                     f"mark (matched_by {rep.matched_by})")
        return [_row(
            "device trace", "", cold_s, run_s, 0.0, n_events=tl.n_events,
            n_devices=tl.n_devices,
            busy_fraction=round(tl.compute_busy_fraction, 4),
            capture=rep.absent or {
                "n_ops": rep.n_ops, "n_marks": rep.n_marks,
                "n_dump_marks": rep.n_dump_marks, "anchor": rep.anchor,
                "matched_by": rep.matched_by,
                "coverage_frac": round(rep.coverage_frac, 4),
                "devices": rep.devices})]
    finally:
        if cr.enqueue_mode:
            cr.enqueue_mode = False
        cr.dispose()
        shutil.rmtree(root, ignore_errors=True)


STAGES = (
    ("1 compute()", stage_compute),
    ("2 transfers", stage_transfers),
    ("3 pipeline", stage_pipeline),
    ("4 serving", stage_serving),
    ("5 kernels", stage_kernels),
    ("6 device trace", stage_trace),
)


def _cache_files(path: str | None) -> int:
    """Executables in jax's persistent cache (its ``<key>-cache`` files;
    the eviction's ``-atime`` stamps and lock file are not entries)."""
    if not path or not os.path.isdir(path):
        return 0
    return sum(name.endswith("-cache") for name in os.listdir(path))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        import jax

        import cekirdekler_tpu as ct
        from cekirdekler_tpu import native
    except ImportError as e:
        print(f"chip_smoke: FAIL — the program is not here ({e}); run it "
              "from the root of a checkout", file=sys.stderr)
        return 2

    tpus = ct.all_devices().tpus()
    if not len(tpus):
        print(f"chip_smoke: FAIL — no TPU found (jax sees "
              f"{[str(d) for d in jax.devices()]}); this check does not "
              "run on CPU devices", file=sys.stderr)
        return 2
    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices())}
    try:
        import libtpu

        libtpu_v = getattr(libtpu, "__version__", "?")
    except ImportError:
        libtpu_v = "absent"
    import jaxlib

    cache_dir = jax.config.jax_compilation_cache_dir
    files0 = _cache_files(cache_dir)
    print(f"chip_smoke: {device['count']} x {device['kind']} "
          f"({device['platform']}); jax {jax.__version__} jaxlib "
          f"{jaxlib.__version__} libtpu {libtpu_v}")
    print(f"  lanes: {[d.name for d in tpus]}")
    print(f"  host runtime: {native.runtime()}")
    placed_by = ("JAX_COMPILATION_CACHE_DIR"
                 if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 else "package default")
    print(f"  compile cache: {cache_dir} ({files0} executables; {placed_by})")

    sizes = dict(FULL, seed=args.seed)
    failures: list[str] = []
    t_all = time.perf_counter()
    compile_s = 0.0
    for name, fn in STAGES:
        t0 = time.perf_counter()
        try:
            rows = fn(tpus, sizes)
        except Exception as e:  # noqa: BLE001 - a raising stage fails the run
            import traceback

            traceback.print_exc()
            failures.append(f"stage {name}: {type(e).__name__}: {e}")
            print(f"[stage {name}] FAIL after "
                  f"{time.perf_counter() - t0:.1f}s: {type(e).__name__}: "
                  f"{str(e)[:600]}", flush=True)
            continue
        print(f"[stage {name}] ok in {time.perf_counter() - t0:.1f}s",
              flush=True)
        for r in rows:
            compile_s += max(r["cold_s"] - r["run_s"], 0.0)
            extra = {k: v for k, v in r.items() if k not in (
                "name", "lowering", "cold_s", "run_s", "max_err")}
            print(f"  {r['name']:<34} lowering={r['lowering'] or '-':<10} "
                  f"cold={r['cold_s']:8.3f}s run={r['run_s']:9.4f}s "
                  f"max_err={r['max_err']:.3g}"
                  + (f"  {json.dumps(extra)}" if extra else ""), flush=True)
    print(f"chip_smoke: {time.perf_counter() - t_all:.1f}s total, "
          f"~{compile_s:.1f}s of it first-call (compile) cost; compile "
          f"cache now {_cache_files(cache_dir)} executables (was {files0})")
    if failures:
        for f in failures:
            print(f"chip_smoke: FAIL — {f}", file=sys.stderr)
        print(json.dumps({"ok": False, "device": device,
                          "failures": [f[:300] for f in failures]}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
