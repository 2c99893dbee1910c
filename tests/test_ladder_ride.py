"""A multi-rung per-call launch rides the fused ladder executable
(``Worker.launch`` -> ``KernelProgram.fused_launcher(..., build=False)``):

- ONE dispatch (``iters=1``) in place of the host loop over the rungs, only
  when the ladder has more than one rung AND a fused window or
  ``Cores.warmup`` has already built the executable of exactly this key;
- bit-identical to the rung loop whatever the offset and the unit count;
- the per-call path never builds that executable: with none in the cache
  it loops as it always did, whatever the scalars do from call to call;
- the four-chip cell's sequence (window, barrier, range move, next window)
  on the CPU rig: the window's one per-call compute is ``x1`` on every lane.

Counts and bit-identity only — the rig proves no time (PERF.md s.6, PR 25
holds the chip's numbers)."""

import time

import numpy as np
import pytest

from cekirdekler_tpu import ClArray
from cekirdekler_tpu.core import NumberCruncher
from cekirdekler_tpu.core.compilecache import WarmupSpec
from cekirdekler_tpu.core.worker import Worker, launch_ladder
from cekirdekler_tpu.hardware import platforms
from cekirdekler_tpu.kernel.registry import KernelProgram
from cekirdekler_tpu.trace.spans import tracing

SRC = """
__kernel void axpb(__global float* a, float s, float t) {
    int i = get_global_id(0);
    a[i] = a[i] * s + t * (float)i;
}
__kernel void orbit(__global float* x, __global float* y, float c) {
    int i = get_global_id(0);
    float z = x[i];
    for (int k = 0; k < 7; k++) { z = z * z * 0.25f + c; }
    y[i] = z + sqrt(x[i]);
}
"""
STEP = 64
UNITS = 16                # the global range in steps of 64
GLOBAL = UNITS * STEP
#: kernel -> (arrays, the scalars of a call)
KERNELS = {"axpb": (1, (1.5, 0.125)), "orbit": (2, (0.3,))}
#: (offset units, units): one rung, one rung off zero, two rungs, two rungs
#: off zero, all bits set, all but the lowest, the global range
SWEEP = [(0, 1), (5, 8), (0, 3), (3, 12), (0, 15), (1, 14), (0, 16)]


@pytest.fixture(scope="module")
def devs():
    return platforms().cpus()


def launch_tags(spans):
    return [s.tag for s in spans if s.kind == "launch"]


def one_launch(kernel, offset_units, units, built: bool):
    """One ``Worker.launch`` over [offset, offset + units) steps of a
    fresh program, with or without its fused executable in the cache;
    returns (the arrays afterwards, the span's tag, fused executables)."""
    n_arr, values = KERNELS[kernel]
    prog = KernelProgram(SRC)
    w = Worker(platforms().cpus().subset(1)[0].jax_device, 0)
    rng = np.random.default_rng(7)
    arrays = [ClArray(rng.random(GLOBAL, dtype=np.float32) + 0.5,
                      name=f"a{i}") for i in range(n_arr)]
    for a in arrays:
        w.upload(a, 0, GLOBAL, True)
    if built:
        # what a fused window's first dispatch does: BUILD this key
        assert prog.fused_launcher(
            (kernel,), STEP, GLOBAL, STEP, GLOBAL, values,
            platform=w.device.platform, donate=w.fused_donate) is not None
    with tracing() as tr:
        w.launch(prog, [kernel], arrays, values, offset_units * STEP,
                 units * STEP, STEP, GLOBAL, STEP, compute_id=1)
    (tag,) = launch_tags(tr.snapshot())
    out = [np.asarray(w.buffer(a)) for a in arrays]
    w.dispose()
    return out, tag, prog.fused_compiled_count


@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("offset_units,units", SWEEP)
def test_one_dispatch_is_bit_identical_to_the_rung_loop(
        kernel, offset_units, units):
    rungs = len(launch_ladder(units * STEP, STEP))
    looped, tag_loop, fused_loop = one_launch(kernel, offset_units, units,
                                              built=False)
    rode, tag_ride, fused_ride = one_launch(kernel, offset_units, units,
                                            built=True)
    # no executable in the cache: the loop, one dispatch a rung, none built
    assert (tag_loop, fused_loop) == (f"{kernel} x{rungs}", 0)
    # the executable is there: ONE dispatch — and a single rung, which is
    # one dispatch already, is left on its own launcher
    assert (tag_ride, fused_ride) == (f"{kernel} x1", 1)
    for a, b in zip(looped, rode):
        assert a.tobytes() == b.tobytes()
    # the launch did something, and only inside its range
    lo, hi = offset_units * STEP, (offset_units + units) * STEP
    fresh = np.random.default_rng(7).random(GLOBAL, dtype=np.float32) + 0.5
    written = rode[-1]
    if len(rode) == 1:
        assert not np.array_equal(written[lo:hi], fresh[lo:hi])
        np.testing.assert_array_equal(written[:lo], fresh[:lo])
        np.testing.assert_array_equal(written[hi:], fresh[hi:])
    else:
        np.testing.assert_array_equal(rode[0], fresh)  # read-only input


def test_single_rung_launch_does_not_touch_the_fused_executable(devs):
    """One rung is one dispatch already: it goes to its own launcher even
    with the fused executable built (what keeps the n-body cells and the
    streamed chunks where they were)."""
    prog = KernelProgram(SRC)
    w = Worker(devs.subset(1)[0].jax_device, 0)
    a = ClArray(np.ones(GLOBAL, np.float32), name="a")
    w.upload(a, 0, GLOBAL, True)
    values = KERNELS["axpb"][1]
    prog.fused_launcher(("axpb",), STEP, GLOBAL, STEP, GLOBAL, values,
                        platform=w.device.platform, donate=w.fused_donate)
    before = prog.compiled_count
    w.launch(prog, ["axpb"], [a], values, 0, 8 * STEP, STEP, GLOBAL, STEP)
    # the fused executable was never traced (its rungs would have been
    # built inside it): exactly the one standalone rung appeared
    assert prog.compiled_count == before + 1
    assert ("axpb", 8 * STEP, STEP, GLOBAL, w.device.platform) in prog._cache
    w.dispose()


@pytest.mark.parametrize("repeats,sync_kernel,values", [
    (3, None, KERNELS["axpb"][1]),          # repeat mode
    (1, "axpb", KERNELS["axpb"][1]),        # a sync kernel is set
    (1, None, ([1.5], [0.125])),            # unhashable values: no key
])
def test_other_paths_stay_where_they_were(devs, repeats, sync_kernel, values):
    prog = KernelProgram(SRC)
    w = Worker(devs.subset(1)[0].jax_device, 0)
    a = ClArray(np.ones(GLOBAL, np.float32), name="a")
    w.upload(a, 0, GLOBAL, True)
    prog.fused_launcher(("axpb",), STEP, GLOBAL, STEP, GLOBAL,
                        KERNELS["axpb"][1], platform=w.device.platform,
                        donate=w.fused_donate)
    with tracing() as tr:
        w.launch(prog, ["axpb"], [a], values, 0, 3 * STEP, STEP, GLOBAL,
                 STEP, repeats=repeats, sync_kernel=sync_kernel)
    (tag,) = launch_tags(tr.snapshot())
    # repeat mode is one sequence dispatch as before; the others loop
    assert tag == ("axpb x1" if repeats > 1 else "axpb x2")
    assert prog.fused_compiled_count == 1
    w.dispose()


def test_per_call_path_never_builds_the_fused_executable(devs):
    """Non-windowed computes on two lanes with uneven (multi-rung) shares
    and a scalar that changes every call: the loop, ``dispatched`` equal
    to the rung count, no fused executable — a per-call path that built
    one would compile at every new value."""
    cr = NumberCruncher(devs.subset(2), SRC)
    # the monolithic phase: the streaming engine launches chunk by chunk,
    # each chunk a single ladder-aligned rung (tests/test_stream.py)
    cr.streamed_transfers = False
    prog = cr.cores.program
    a = ClArray(np.ones(GLOBAL * 3, np.float32), name="a")
    a.partial_read = True
    expect = np.ones(GLOBAL * 3, np.float32)
    idx = np.arange(GLOBAL * 3, dtype=np.float32)
    with tracing() as tr:
        for call, s in enumerate((2.0, 4.0, 0.5, 8.0, 2.0)):
            a.compute(cr, 7, "axpb", GLOBAL * 3, STEP, values=(s, 1.0))
            expect = expect * np.float32(s) + idx
            ranges = cr.ranges_of(7)
            assert sum(ranges) == GLOBAL * 3
            tags = launch_tags(tr.snapshot())[-len(ranges):]
            assert sorted(tags) == sorted(
                f"axpb x{len(launch_ladder(r, STEP))}" for r in ranges)
            assert prog.fused_compiled_count == 0, call
    # 48 units over two lanes: 24 + 24 or a moved split — never one rung
    assert any(not t.endswith(" x1") for t in launch_tags(tr.snapshot()))
    np.testing.assert_array_equal(np.asarray(a), expect)
    cr.dispose()


def test_warmup_builds_the_key_the_per_call_path_rides(devs):
    """``Cores.warmup`` builds the fused key, so a warmed cruncher's
    multi-rung per-call launches are one dispatch each — with the same
    results, and only while the values are the warmed ones."""
    n = GLOBAL * 3                    # 48 units: 24 + 24, two rungs a lane
    host = np.random.default_rng(3).random(n, dtype=np.float32)

    def run(warm: bool):
        cr = NumberCruncher(devs.subset(2), SRC)
        cr.streamed_transfers = False
        a = ClArray(host.copy(), name="a")
        a.partial_read = True
        if warm:
            cr.cores.warmup([WarmupSpec.from_job(
                ["axpb"], [a], 9, n, STEP, 0, (1.5, 0.125))])
        fused0 = cr.cores.program.fused_compiled_count
        with tracing() as tr:
            a.compute(cr, 9, "axpb", n, STEP, values=(1.5, 0.125))
            first = launch_tags(tr.snapshot())
            a.compute(cr, 9, "axpb", n, STEP, values=(2.5, 0.125))
            second = launch_tags(tr.snapshot())[len(first):]
        # other values have no key: the loop, at whatever the split is now
        assert sorted(second) == sorted(
            f"axpb x{len(launch_ladder(r, STEP))}" for r in cr.ranges_of(9))
        assert cr.cores.program.fused_compiled_count == fused0 == int(warm)
        out = np.asarray(a).copy()
        cr.dispose()
        return first, out

    loop_first, looped = run(warm=False)
    ride_first, rode = run(warm=True)
    assert loop_first == ["axpb x2", "axpb x2"]
    assert ride_first == ["axpb x1", "axpb x1"]
    assert looped.tobytes() == rode.tobytes()


def _cell_sequence(devs, lanes: int, fused: bool):
    """What ``mandelbrot_balance_4chip`` does, at rig size: windows of 6
    computes closed by a barrier, with one lane's fence slowed for the
    second window so that the third starts with a range move."""
    cr = NumberCruncher(devs.subset(lanes), SRC)
    cr.fused_dispatch = fused
    prog = cr.cores.program
    n = 64 * STEP * lanes
    a = ClArray(np.full(n, 0.5, np.float32), name="a")
    a.partial_read = True
    values = (1.0009765625, 0.0001220703125)
    slow = cr.cores.workers[0]
    orig_fence = slow.fence

    def laggy():
        time.sleep(0.2)
        orig_fence()

    log = []
    cr.enqueue_mode = True
    try:
        with tracing() as tr:
            for window in range(4):
                slow.fence = laggy if window == 1 else orig_fence
                mark = len(launch_tags(tr.snapshot()))
                counts = (prog.compiled_count, prog.fused_compiled_count)
                for _ in range(6):
                    a.compute(cr, 31, "axpb", n, STEP, values=values)
                first = [s for s in tr.snapshot() if s.kind == "launch"][
                    mark:mark + lanes]
                cr.barrier()
                log.append({
                    "ranges": cr.ranges_of(31),
                    "first": {s.lane: s.tag for s in first},
                    "built": (prog.compiled_count - counts[0],
                              prog.fused_compiled_count - counts[1]),
                })
    finally:
        slow.fence = orig_fence
        cr.enqueue_mode = False
    stats = cr.fused_stats
    out = np.asarray(a).copy()
    cr.dispose()
    return log, stats, out


@pytest.mark.parametrize("lanes", [2, 4])
def test_cell_sequence_first_compute_of_a_window_is_one_dispatch(devs, lanes):
    log, stats, rode = _cell_sequence(devs, lanes, fused=True)
    _log, _stats, per_iteration = _cell_sequence(devs, lanes, fused=False)
    moved = [i for i in range(1, 4) if log[i]["ranges"] != log[i - 1]["ranges"]]
    assert moved, [w["ranges"] for w in log]
    for i in moved:
        w = log[i]
        # the window ran AT its moved ranges, and some lane's share is a
        # ladder of several rungs: the loop would have read x2 or more
        assert any(len(launch_ladder(r, STEP)) > 1 for r in w["ranges"]), w
        assert w["first"] == {lane: "axpb x1" for lane in range(lanes)}, w
        assert w["built"] == (0, 0), w      # nothing compiles
    # the fused WINDOW counters count window dispatches only, as before:
    # of a window's 6 computes one goes per call and re-engages, 5 defer
    # (4 in the process's first window) and go out as the ramp's x1 x2 and
    # the residue at the barrier: three dispatches a window
    assert stats["windows"] == 4 * 3 and stats["fused_iters"] == 4 * 5 - 1, stats
    # more than one lane: every barrier arms a rebalance, no ladder start
    assert stats["window_starts"] == {"first-sighting": 1, "range-change": 3}
    assert stats["deferred_iters"] == stats["fused_iters"]
    assert rode.tobytes() == per_iteration.tobytes()
