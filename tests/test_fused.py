"""Fused-iteration dispatch path (the enqueue dispatch-floor collapse):
deferral correctness, bit-identity with per-iteration dispatch, the
executable-cache invariant across balancer re-partitioning, named
disengage reasons, and the window-scoped coverage-epoch fix for the r7
KNOWN LIMIT (multi-threaded enqueue windows + sync-point rebalance).

The inc kernel adds exactly 1.0f — small-integer f32 arithmetic is exact,
so every lost/duplicated iteration (and every lost REGION update across a
range move) shows as an integer-sized error and the assertions can demand
bit equality.  Value-varying math is covered by the mandelbrot and n-body
bit-identity tests, which compare the fused path against the per-iteration
path rather than against a host emulation."""

import threading
import time

import numpy as np
import pytest

from cekirdekler_tpu import ClArray
from cekirdekler_tpu.core import NumberCruncher
from cekirdekler_tpu.core.window import job_signature
from cekirdekler_tpu.hardware import platforms

INC = """
__kernel void inc(__global float* a) {
    int i = get_global_id(0);
    a[i] = a[i] + 1.0f;
}
__kernel void dbl(__global float* a) {
    int i = get_global_id(0);
    a[i] = a[i] * 1.001f;
}
"""


@pytest.fixture(scope="module")
def devs():
    return platforms().cpus()


def laggy(orig, secs=0.2):
    def f():
        time.sleep(secs)
        orig()

    return f


# ---------------------------------------------------------------------------
# deferral + correctness
# ---------------------------------------------------------------------------

def test_fused_window_defers_and_is_exact(devs):
    """An enqueue window repeating one cid defers everything after the
    first call and still produces exactly the per-iteration result."""
    cr = NumberCruncher(devs.subset(2), INC)
    x = ClArray(np.zeros(1024, np.float32), name="x")
    x.partial_read = True
    cr.enqueue_mode = True
    iters = 12
    for _ in range(iters):
        x.compute(cr, 1, "inc", 1024, 64)
    # call 1 seeds the candidate, call 2 engages, calls 3..N defer
    assert cr.fused_stats["deferred_iters"] == iters - 2, cr.fused_stats
    # host untouched while deferred (enqueue semantics hold)
    assert np.all(np.asarray(x) == 0.0)
    cr.enqueue_mode = False  # flush dispatches the residue
    assert cr.fused_stats["fused_iters"] == iters - 2
    np.testing.assert_array_equal(np.asarray(x), float(iters))
    cr.dispose()


def test_fused_batches_dispatch_eagerly(devs):
    """The eager sub-batch ramps: the first deferral of a window is
    dispatched alone, then 2, then 4, up to fused_batch (the device starts
    on the window's first deferred iteration), not only at the barrier."""
    cr = NumberCruncher(devs.subset(2), INC)
    cr.fused_batch = 4
    x = ClArray(np.zeros(512, np.float32), name="x")
    x.partial_read = True
    cr.enqueue_mode = True
    # call 1 seeds, call 2 engages, calls 3..11 defer -> eager batches of
    # 1, 2 and 4 mid-window, residue 2 at the barrier
    for _ in range(11):
        x.compute(cr, 1, "inc", 512, 64)
    assert cr.fused_stats["windows"] == 3
    assert cr.fused_stats["fused_iters"] == 7
    cr.barrier()  # residue (2) dispatches at the window close
    assert cr.fused_stats["windows"] == 4
    assert cr.fused_stats["fused_iters"] == 9
    cr.enqueue_mode = False
    np.testing.assert_array_equal(np.asarray(x), 11.0)
    cr.dispose()


def test_fused_is_one_dispatch_per_batch(devs):
    """Marker accounting: a 32-iteration window costs O(log fused_batch)
    dispatches, not O(iterations) — the dispatch-floor collapse made
    observable (same methodology as test_repeat_is_one_fused_dispatch) —
    and a window that repeats it has no per-call launch at all."""
    cr = NumberCruncher(devs.subset(1), INC)
    cr.fine_grained_queue_control = True
    cr.fused_batch = 32
    x = ClArray(np.zeros(256, np.float32), name="x")
    x.partial_read = True
    cr.enqueue_mode = True
    for _ in range(32):
        x.compute(cr, 1, "inc", 256, 64)
    cr.barrier()
    w = cr.cores.workers[0]
    # 1 upload + 2 per-call launches + the ramp's ladders x1 x2 x4 x8 and
    # the residue x15 = 8
    assert w.markers.added == 8, w.markers.added
    for _ in range(32):
        x.compute(cr, 1, "inc", 256, 64)
    cr.barrier()
    # the second window starts on the ladder: x1 x2 x4 x8 x16, residue x1
    assert w.markers.added == 8 + 6, w.markers.added
    assert cr.fused_stats["window_starts"] == {"first-sighting": 1,
                                               "ladder": 1}
    cr.enqueue_mode = False  # + 1 download
    assert w.markers.added == 8 + 6 + 1, w.markers.added
    np.testing.assert_array_equal(np.asarray(x), 64.0)
    cr.dispose()


def test_fused_bit_identical_mandelbrot_image(devs):
    """The acceptance gate: the fused path's mandelbrot image is
    BIT-identical to the per-iteration path's."""
    from cekirdekler_tpu.workloads import MANDELBROT_SRC

    w = h = 256
    n = w * h
    vals = (-2.0, -1.25, 2.5 / w, 2.5 / h, w, 64)
    images = {}
    for fused in (False, True):
        cr = NumberCruncher(devs.subset(2), MANDELBROT_SRC)
        cr.fused_dispatch = fused
        out = ClArray(n, np.float32, name=f"m{fused}", read=False, write=True)
        cr.enqueue_mode = True
        for _ in range(5):
            out.compute(cr, 31, "mandelbrot", n, 256, values=vals)
        cr.enqueue_mode = False
        if fused:
            assert cr.fused_stats["fused_iters"] > 0
        else:
            assert cr.fused_stats["fused_iters"] == 0
        images[fused] = np.asarray(out).copy()
        cr.dispose()
    np.testing.assert_array_equal(images[True], images[False])


def test_fused_bit_identical_accumulating_nbody(devs):
    """Accumulating state (the n-body velocity integral): K fused
    iterations equal K per-iteration dispatches bit-for-bit."""
    from cekirdekler_tpu.workloads import NBODY_SRC, _nbody_rig

    n, iters = 512, 8
    results = {}
    for fused in (False, True):
        _, (x, y, z), vel = _nbody_rig(n, f"f{int(fused)}")
        cr = NumberCruncher(devs.subset(2), NBODY_SRC)
        cr.fused_dispatch = fused
        g = x.next_param(y, z, *vel)
        cr.enqueue_mode = True
        for _ in range(iters):
            g.compute(cr, 32, "nBody", n, 64, values=(n, 1e-4))
        cr.enqueue_mode = False
        results[fused] = [np.asarray(v).copy() for v in vel]
        cr.dispose()
    for a, b in zip(results[True], results[False]):
        np.testing.assert_array_equal(a, b)


def test_fused_mixed_cids_and_fence_split(devs):
    """Alternating cids breaks fusion per switch (signature-change) but
    stays exact; fence_split's per-cid completion probes survive the
    fused launches (donation is disabled while probes are pinned)."""
    cr = NumberCruncher(devs.subset(2), INC)
    cr.fence_split = True
    x = ClArray(np.zeros(512, np.float32), name="x")
    x.partial_read = True
    y = ClArray(np.ones(512, np.float32), name="y")
    y.partial_read = True
    cr.enqueue_mode = True
    for _ in range(3):
        for _ in range(4):
            x.compute(cr, 41, "inc", 512, 64)
        for _ in range(4):
            y.compute(cr, 42, "dbl", 512, 64)
    cr.barrier()
    cr.enqueue_mode = False
    dis = cr.fused_stats["disengaged"]
    assert dis.get("signature-change", 0) >= 5, dis
    assert cr.fused_stats["fused_iters"] > 0
    np.testing.assert_array_equal(np.asarray(x), 12.0)
    np.testing.assert_allclose(
        np.asarray(y), np.float32(1.001) ** 12, rtol=1e-5
    )
    cr.dispose()


# ---------------------------------------------------------------------------
# executable-cache keying (satellite: compile-count invariant)
# ---------------------------------------------------------------------------

def test_fused_executable_cache_survives_rebalance(devs):
    """Compile count stays FLAT across a forced rebalance (range shift,
    unchanged shapes) and the fused executable count increments exactly
    once on a genuine shape change — the executable-cache keying
    contract (offset/units/iteration-count are runtime arguments of one
    cached ladder)."""
    cr = NumberCruncher(devs.subset(2), INC)
    prog = cr.cores.program
    x = ClArray(np.zeros(4096, np.float32), name="x")
    x.partial_read = True
    cr.enqueue_mode = True
    slow = cr.cores.workers[0]
    orig_fence = slow.fence
    total = 0
    try:
        for _ in range(3):
            x.compute(cr, 51, "inc", 4096, 64)
            total += 1
        cr.barrier()
        warm_fused = prog.fused_compiled_count
        warm_total = prog.compiled_count
        assert warm_fused == 1
        # force a genuine range shift: the slow chip must lose share
        slow.fence = laggy(orig_fence)
        for _ in range(3):
            x.compute(cr, 51, "inc", 4096, 64)
            total += 1
        cr.barrier()
        slow.fence = orig_fence
        before_move = cr.ranges_of(51)
        for _ in range(3):  # first call rebalances (armed), then re-fuses
            x.compute(cr, 51, "inc", 4096, 64)
            total += 1
        cr.barrier()
        moved = cr.ranges_of(51)
        assert moved != before_move, (before_move, moved)
        # the invariant: re-partitioning hit the cache, no recompile —
        # neither a new fused ladder nor any new per-chunk geometry
        assert prog.fused_compiled_count == warm_fused
        assert prog.compiled_count == warm_total
        # a genuine shape change compiles exactly one new fused ladder
        y = ClArray(np.zeros(8192, np.float32), name="y")
        y.partial_read = True
        for _ in range(3):
            y.compute(cr, 52, "inc", 8192, 64)
        cr.barrier()  # fused build happens at the window dispatch
        assert prog.fused_compiled_count == warm_fused + 1
    finally:
        slow.fence = orig_fence
        cr.enqueue_mode = False
    np.testing.assert_array_equal(np.asarray(x), float(total))
    cr.dispose()


# ---------------------------------------------------------------------------
# named disengage reasons (satellite: no silent fallback)
# ---------------------------------------------------------------------------

def _tracer_disengages():
    from cekirdekler_tpu.trace.spans import TRACER

    return [
        s.tag for s in TRACER.snapshot()
        if s.kind == "fused" and (s.tag or "").startswith("disengage:")
    ]


def test_disengage_range_change_is_named(devs):
    """An armed rebalance (range change at the window boundary) breaks
    the fused run with reason "range-change" — and emits a trace
    instant."""
    from cekirdekler_tpu.trace.spans import TRACER

    cr = NumberCruncher(devs.subset(2), INC)
    x = ClArray(np.zeros(4096, np.float32), name="x")
    x.partial_read = True
    cr.enqueue_mode = True
    slow = cr.cores.workers[0]
    orig = slow.fence
    slow.fence = laggy(orig)
    TRACER.enable(clear=True)
    try:
        for _ in range(3):
            x.compute(cr, 61, "inc", 4096, 64)
        cr.barrier()  # arms the rebalance
        slow.fence = orig
        # sig from the new window's first call matches nothing (window
        # closed at the barrier), so re-engage, then defer, then break on
        # the SECOND window boundary?  No: the armed flag is consumed by
        # the first call after the barrier — which therefore cannot have
        # an active fused sig.  Drive one engage + one armed break:
        x.compute(cr, 61, "inc", 4096, 64)  # armed rebalance consumed here
        x.compute(cr, 61, "inc", 4096, 64)  # defers
        cr.cores._window.rebalance.add(61)  # re-arm mid-window (as a
        # concurrent thread's barrier would)
        x.compute(cr, 61, "inc", 4096, 64)  # breaks: range-change
        assert cr.fused_stats["disengaged"].get("range-change", 0) == 1
        assert any("range-change" in t for t in _tracer_disengages())
    finally:
        TRACER.disable()
        slow.fence = orig
        cr.enqueue_mode = False
    np.testing.assert_array_equal(np.asarray(x), 6.0)
    cr.dispose()


def test_disengage_non_resident_is_named(devs):
    """A coverage-epoch bump mid-window (what every reset_coverage()
    does) disengages with reason "non-resident" and results stay exact."""
    cr = NumberCruncher(devs.subset(2), INC)
    x = ClArray(np.zeros(1024, np.float32), name="x")
    x.partial_read = True
    cr.enqueue_mode = True
    for _ in range(3):
        x.compute(cr, 62, "inc", 1024, 64)
    assert cr.cores._window.sig is not None
    for w in cr.cores.workers:
        w.coverage_epoch += 1  # the observable effect of reset_coverage()
    x.compute(cr, 62, "inc", 1024, 64)
    assert cr.fused_stats["disengaged"].get("non-resident", 0) == 1
    cr.enqueue_mode = False
    np.testing.assert_array_equal(np.asarray(x), 4.0)
    cr.dispose()


def test_disengage_pipeline_and_repeat_are_named(devs):
    """Pipelined enqueue calls and repeat-mode calls refuse fusion with
    their own reasons (each already fuses internally or blobs)."""
    cr = NumberCruncher(devs.subset(2), INC)
    x = ClArray(np.zeros(2048, np.float32), name="x")
    x.partial_read = True
    cr.enqueue_mode = True
    x.compute(cr, 63, "inc", 2048, 64)  # seeds
    x.compute(cr, 63, "inc", 2048, 64)  # engages
    x.compute(cr, 63, "inc", 2048, 64, pipeline=True, pipeline_blobs=4)
    assert cr.fused_stats["disengaged"].get("pipeline", 0) >= 1
    cr.repeat_count = 3
    x.compute(cr, 63, "inc", 2048, 64)  # refused while repeat-mode is on
    assert cr.fused_stats["disengaged"].get("repeat-mode", 0) >= 1
    cr.repeat_count = 1
    cr.enqueue_mode = False
    np.testing.assert_array_equal(np.asarray(x), 6.0)
    cr.dispose()


def test_disengage_mode_change_mid_window(devs):
    """Runtime mode toggles are NOT in the window signature — flipping
    one mid-window must break the run ("mode-change"), not defer a call
    whose semantics changed.  repeat_count=3 mid-window must apply 3
    on-device repeats (deferred, it would count as ONE); no_compute_mode
    mid-window must skip compute entirely; a dispatch_gate must hold."""
    cr = NumberCruncher(devs.subset(2), INC)
    x = ClArray(np.zeros(512, np.float32), name="x")
    x.partial_read = True
    cr.enqueue_mode = True
    x.compute(cr, 66, "inc", 512, 64)  # engages
    x.compute(cr, 66, "inc", 512, 64)  # defers
    cr.repeat_count = 3
    x.compute(cr, 66, "inc", 512, 64)  # 3 repeats, must NOT defer as 1
    assert cr.fused_stats["disengaged"].get("mode-change", 0) == 1
    cr.repeat_count = 1
    x.compute(cr, 66, "inc", 512, 64)  # re-engages
    x.compute(cr, 66, "inc", 512, 64)  # defers
    cr.no_compute_mode = True
    x.compute(cr, 66, "inc", 512, 64)  # I/O only, must NOT defer
    assert cr.fused_stats["disengaged"].get("mode-change", 0) == 2
    cr.no_compute_mode = False
    cr.enqueue_mode = False
    np.testing.assert_array_equal(np.asarray(x), 7.0)  # 1+1+3+1+1+0
    cr.dispose()


def test_disengage_partial_upload_guard(devs):
    """The engage-time coverage guard: a read param whose chip range is
    not fully covered refuses engagement with reason "partial-upload"
    (unit-level: the builtin upload path leaves ranges covered, so the
    refusal is rigged via a shrunk coverage record)."""
    cr = NumberCruncher(devs.subset(2), INC)
    cores = cr.cores
    x = ClArray(np.zeros(1024, np.float32), name="x")
    x.partial_read = True
    cr.enqueue_mode = True
    x.compute(cr, 64, "inc", 1024, 64)  # seeds the candidate
    x.compute(cr, 64, "inc", 1024, 64)  # consecutive repeat -> engages
    assert cores._window.sig is not None
    cores._window.close()
    w = cores.workers[0]
    with w.lock:
        off, _ = w._uploaded[id(x)]
        w._uploaded[id(x)] = (off, 1)
    cores._window.try_engage(
        job_signature(["inc"], [x], 64, 1024, 64, 0, ()),
        ["inc"], [x], 64, 1024, 64, 0, (),
        cores.global_ranges[64], cores.global_references[64], 64,
    )
    assert cores._window.sig is None
    assert cr.fused_stats["disengaged"].get("partial-upload", 0) == 1
    cr.enqueue_mode = False
    cr.dispose()


def test_disengage_unhashable_values(devs):
    """Unhashable value args cannot bake into the fused executable —
    refusal reason "unhashable-values", per-iteration results exact."""
    src = """
    __kernel void axb(__global float* x, float aa) {
        int i = get_global_id(0);
        x[i] = x[i] + aa;
    }"""
    cr = NumberCruncher(devs.subset(2), src)
    x = ClArray(np.zeros(256, np.float32), name="x")
    x.partial_read = True
    cr.enqueue_mode = True

    class UnhashableFloat(float):
        __hash__ = None

    for _ in range(3):
        x.compute(cr, 65, "axb", 256, 64, values=(UnhashableFloat(2.0),))
    assert cr.fused_stats["disengaged"].get("unhashable-values", 0) >= 1
    assert cr.fused_stats["fused_iters"] == 0
    cr.enqueue_mode = False
    np.testing.assert_array_equal(np.asarray(x), 6.0)
    cr.dispose()


# ---------------------------------------------------------------------------
# how a window starts (PR 34): on the ladder where it repeats the last one
# ---------------------------------------------------------------------------

ACC = """
__kernel void acc(__global float* p, __global float* v, float dt) {
    int i = get_global_id(0);
    v[i] = v[i] + dt * (p[i] - 0.37f * v[i]);
}
"""


def _window(cr, compute, computes, lanes):
    for _ in range(computes):
        compute()
    cr.barrier()
    if lanes > 1:
        # every barrier of more than one lane arms a rebalance; the rig
        # stands for a balancer that has nothing to move
        cr.cores._window.rebalance.clear()


def _fused_tags():
    from cekirdekler_tpu.trace.spans import TRACER

    return [int(s.tag[1:]) for s in TRACER.snapshot()
            if s.kind == "fused" and (s.tag or "").startswith("x")]


@pytest.mark.parametrize("lanes", [1, 2])
def test_window_repeating_the_last_starts_on_the_ladder(devs, lanes):
    """Three windows of an accumulating kernel: the second and the third
    start on the ladder (their first compute is deferred like the others),
    a flush() after such a window brings every iteration back, and the
    state is bit-identical to fused_dispatch = False."""
    n, computes = 1024, 6
    rng = np.random.default_rng(5)
    pos = rng.standard_normal(n).astype(np.float32)
    left = {}
    for fused in (False, True):
        cr = NumberCruncher(devs.subset(lanes), ACC)
        cr.fused_dispatch = fused
        p = ClArray(pos.copy(), name="p", read_only=True)
        v = ClArray(np.zeros(n, np.float32), name="v", partial_read=True)
        g = p.next_param(v)

        def compute():
            g.compute(cr, 34, "acc", n, 64, values=(1e-2,))

        cr.enqueue_mode = True
        _window(cr, compute, computes, lanes)
        _window(cr, compute, computes, lanes)
        cr.flush()  # after a window that started on the ladder
        states = [np.asarray(v).copy()]
        _window(cr, compute, computes, lanes)
        cr.enqueue_mode = False
        states.append(np.asarray(v).copy())
        left[fused] = states
        starts = cr.fused_stats["window_starts"]
        if fused:
            assert starts == {"first-sighting": 1, "ladder": 2}, starts
            # windows 2 and 3 deferred every compute, window 1 all but two
            assert cr.fused_stats["deferred_iters"] == 3 * computes - 2
            assert cr.fused_stats["fused_iters"] == 3 * computes - 2
        else:
            assert starts == {"mode": 3}, starts
            assert cr.fused_stats["fused_iters"] == 0
        cr.dispose()
    assert np.abs(left[True][0]).max() > 0
    for a, b in zip(left[True], left[False]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("computes,fused_batch,ramp", [
    (50, 16, [1, 2, 4, 8, 16, 16, 3]),
    (10, 16, [1, 2, 4, 3]),
    (20, 4, [1, 2, 4, 4, 4, 4, 1]),
    (7, 16, [1, 2, 4]),
])
def test_the_eager_sub_batch_ramps(devs, computes, fused_batch, ramp):
    """A window that starts on the ladder dispatches its computes as
    x1 x2 x4 .. up to fused_batch, the residue at the barrier: the same
    dispatches in every window, and they add up to its computes."""
    from cekirdekler_tpu.trace.spans import TRACER

    cr = NumberCruncher(devs.subset(1), INC)
    cr.fused_batch = fused_batch
    x = ClArray(np.zeros(256, np.float32), name="x", partial_read=True)

    def compute():
        x.compute(cr, 35, "inc", 256, 64)

    cr.enqueue_mode = True
    _window(cr, compute, computes, 1)
    try:
        for _ in range(2):
            TRACER.enable(clear=True)
            _window(cr, compute, computes, 1)
            assert _fused_tags() == ramp
            assert sum(ramp) == computes
    finally:
        TRACER.disable()
    cr.enqueue_mode = False
    np.testing.assert_array_equal(np.asarray(x), 3.0 * computes)
    cr.dispose()


def _spy_starts(monkeypatch) -> list:
    """Switches the tracer on; returns the list that takes the ``(start,
    tag)`` of every ``enqueue`` span recorded with a ``start`` field (the
    ring's spans have no room for fields)."""
    from cekirdekler_tpu.trace.spans import TRACER

    seen: list = []
    record = TRACER.record

    def spy(kind, t0, *a, **kw):
        if kind == "enqueue" and t0 and "start" in kw:
            seen.append((kw["start"], kw.get("tag", "")))
        return record(kind, t0, *a, **kw)

    monkeypatch.setattr(TRACER, "record", spy)
    TRACER.enable(clear=True)
    return seen


def _start_case(devs, monkeypatch, lanes=1, src=INC):
    """A cruncher in enqueue mode, one array, and the ``start`` fields of
    the ``enqueue`` spans recorded from now on."""
    cr = NumberCruncher(devs.subset(lanes), src)
    x = ClArray(np.zeros(1024, np.float32), name="x", partial_read=True)
    seen = _spy_starts(monkeypatch)
    cr.enqueue_mode = True
    return cr, x, seen


def _values_changed(cr, x, w):
    w(lambda: x.compute(cr, 36, "axb", 1024, 64, values=(1.0,)))
    w(lambda: x.compute(cr, 36, "axb", 1024, 64, values=(2.0,)))
    return 3.0 * 4


def _reupload(cr, x, w):
    inc = lambda: x.compute(cr, 36, "inc", 1024, 64)  # noqa: E731
    w(inc)
    cr.enqueue_mode = False  # flushes: the host holds 4
    x.host()[:] = 10.0
    inc()  # a synchronous compute uploads the state anew: 11
    cr.enqueue_mode = True
    w(inc)
    return 15.0


def _armed_rebalance(cr, x, w):
    inc = lambda: x.compute(cr, 36, "inc", 1024, 64)  # noqa: E731
    w(inc)
    w(inc)  # two lanes: the barrier before it armed a rebalance
    return 8.0


def _one_compute(cr, x, w):
    inc = lambda: x.compute(cr, 36, "inc", 1024, 64)  # noqa: E731
    for _ in range(3):
        inc()
        cr.barrier()
    prog, lane = cr.cores.program, cr.cores.workers[0]
    # no ladder executable was built for a window that never deferred
    assert prog.fused_compiled_count == 0
    assert prog.fused_launcher(
        ("inc",), 64, 1024, 64, 1024, (), platform=lane.device.platform,
        donate=lane.fused_donate, build=False) is None
    return 3.0


def _mode_toggle(cr, x, w):
    inc = lambda: x.compute(cr, 36, "inc", 1024, 64)  # noqa: E731
    w(inc)
    cr.repeat_count = 2
    w(inc)
    cr.repeat_count = 1
    return 4.0 + 8.0


def _shrunk_coverage(cr, x, w):
    inc = lambda: x.compute(cr, 36, "inc", 1024, 64)  # noqa: E731
    w(inc)
    lane = cr.cores.workers[0]
    with lane.lock:
        lane._uploaded[id(x)] = (0, 1)
    w(inc)
    # the per-call start uploads what the lane is said to lack from the
    # host, which a window without a flush has not reached: the rig's
    # doing, and what the per-call path always did with such a record
    return 4.0


AXB = INC + """
__kernel void axb(__global float* a, float b) {
    int i = get_global_id(0);
    a[i] = a[i] + b;
}
"""


@pytest.mark.parametrize("reason,lanes,drive,first", [
    ("values-changed", 1, _values_changed, "first-sighting"),
    ("non-resident", 1, _reupload, "first-sighting"),
    ("range-change", 2, _armed_rebalance, "first-sighting"),
    ("never-fused", 1, _one_compute, "first-sighting"),
    ("mode", 1, _mode_toggle, "first-sighting"),
    ("partial-upload", 1, _shrunk_coverage, "first-sighting"),
])
def test_a_per_call_window_start_is_named(devs, monkeypatch, reason, lanes,
                                          drive, first):
    """Every way a window can fail to start on the ladder takes the
    per-call path as before, counted under its name and said on the
    window's first ``enqueue`` span; no window here starts on the
    ladder, and the results are exact."""
    from cekirdekler_tpu.trace.spans import TRACER

    cr, x, seen = _start_case(devs, monkeypatch, lanes, AXB)

    def window(compute, computes=4):
        for _ in range(computes):
            compute()
        cr.barrier()

    try:
        want = drive(cr, x, window)
    finally:
        TRACER.disable()
    starts = dict(cr.fused_stats["window_starts"])
    assert starts.pop(first) == 1
    assert set(starts) == {reason} and "ladder" not in starts, starts
    assert [s for s, _tag in seen][1:] == [f"per-call:{reason}"] * starts[reason]
    assert not any(tag.endswith("fused-defer") for _s, tag in seen)
    cr.enqueue_mode = False
    np.testing.assert_array_equal(np.asarray(x), want)
    cr.dispose()


def test_an_exchanging_window_starts_per_call_named_halo(devs, monkeypatch):
    """A compute that reads across lanes never fuses: its windows start
    per call, named ``halo``."""
    from cekirdekler_tpu.trace.spans import TRACER

    src = """
    __kernel void shift(__global float* a, __global float* b) {
        int i = get_global_id(0);
        b[i] = a[i + 1] + 1.0f;
    }
    __kernel void back(__global float* a, __global float* b) {
        int i = get_global_id(0);
        a[i] = b[i];
    }"""
    n = 1024
    cr = NumberCruncher(devs.subset(2), src)
    a = ClArray(np.arange(n + 1, dtype=np.float32), name="a",
                partial_read=True)
    b = ClArray(np.zeros(n, np.float32), name="b", read=False)
    g = a.next_param(b)
    seen = _spy_starts(monkeypatch)
    try:
        cr.enqueue_mode = True
        for _ in range(3):
            for _ in range(3):
                g.compute(cr, 37, "shift back", n, 64)
            cr.barrier()
        cr.enqueue_mode = False
    finally:
        TRACER.disable()
    assert cr.fused_stats["window_starts"] == {"halo": 3}
    assert [start for start, _tag in seen] == ["per-call:halo"] * 3
    assert cr.fused_stats["fused_iters"] == 0
    # nine steps of a[i] = a[i + 1] + 1
    want = np.arange(n + 1, dtype=np.float32)
    want[:n - 9 + 1] = (want[9:] + 9.0)[:n - 9 + 1]
    np.testing.assert_array_equal(np.asarray(a)[:n - 9], want[:n - 9])
    cr.dispose()


_PHASE = ("phase-start", "phase-locked", "phase-done")


def _marks(spans):
    return [s for s in spans if (s.tag or "").startswith("part:")
            or s.tag == "retired" or s.tag in _PHASE]


def _launch_pairs(marks):
    """The ``part:call`` / ``part:handed`` pairs of the lanes' launches
    (ISSUE 52: ``engage`` instants with a lane), taken out of ``marks``."""
    pairs = [s for s in marks if s.kind == "engage" and s.lane is not None]
    return pairs, [s for s in marks if s not in pairs]


@pytest.mark.parametrize("lanes", [1, 2])
def test_a_window_on_the_ladder_marks_its_barrier_and_nothing_else(devs,
                                                                   lanes):
    """A deferred compute returns before any mark's site: a window that
    started on the ladder holds the barrier's marks (on one lane: ``wait``,
    ``retired``, ``close``: three instants a call) and, since ISSUE 52, ONE
    ``part:call`` / ``part:handed`` pair a fused dispatch a lane, on the
    lane's driver thread: no lane's phase ran, so no ``phase-*``."""
    from cekirdekler_tpu.trace.spans import TRACER

    n, computes = 1024, 6
    cr = NumberCruncher(devs.subset(lanes), ACC)
    p = ClArray(np.ones(n, np.float32), name="p", read_only=True)
    v = ClArray(np.zeros(n, np.float32), name="v", partial_read=True)
    g = p.next_param(v)

    def compute():
        g.compute(cr, 39, "acc", n, 64, values=(1e-2,))

    try:
        cr.enqueue_mode = True
        _window(cr, compute, computes, lanes)
        TRACER.enable()
        _window(cr, compute, computes, lanes)
        spans = TRACER.snapshot()
    finally:
        TRACER.disable()
    assert cr.fused_stats["window_starts"] == {"first-sighting": 1,
                                               "ladder": 1}
    cr.enqueue_mode = False
    cr.dispose()
    assert sum(s.kind == "enqueue" and s.t1 > s.t0 for s in spans) == computes
    pairs, marks = _launch_pairs(_marks(spans))
    fused = [s for s in spans if s.kind == "launch"]
    assert len(pairs) == 2 * len(fused) and fused
    for lane in range(lanes):
        tags = [s.tag for s in pairs if s.lane == lane]
        assert tags == ["part:call", "part:handed"] * (len(tags) // 2)
    assert {s.kind for s in marks} == {"fence"}
    assert [s.tag for s in marks] == (
        ["part:wait"] + ["retired"] * lanes + ["part:feed"] * (lanes > 1)
        + ["part:close"])


def test_an_exchanging_compute_marks_where_its_strips_are_cut(devs):
    """Every compute of a window that reads across lanes goes per call:
    ``stage`` before the strips are cut, ``submit``, ``join``, ``note``,
    and a lane's ``phase-start``, ``phase-locked``, its launch's pair and
    ``phase-done``, each compute; no resync, no mark of one."""
    from cekirdekler_tpu.trace.spans import TRACER

    src = """
    __kernel void shift(__global float* a, __global float* b) {
        int i = get_global_id(0);
        b[i] = a[i + 1] + 1.0f;
    }
    __kernel void back(__global float* a, __global float* b) {
        int i = get_global_id(0);
        a[i] = b[i];
    }"""
    n, computes = 1024, 3
    cr = NumberCruncher(devs.subset(2), src)
    a = ClArray(np.arange(n + 1, dtype=np.float32), name="a",
                partial_read=True)
    b = ClArray(np.zeros(n, np.float32), name="b", read=False)
    g = a.next_param(b)
    try:
        cr.enqueue_mode = True
        g.compute(cr, 40, "shift back", n, 64)
        cr.barrier()
        TRACER.enable()
        for _ in range(computes):
            g.compute(cr, 40, "shift back", n, 64)
        cr.barrier()
        spans = TRACER.snapshot()
    finally:
        TRACER.disable()
    cr.enqueue_mode = False
    cr.dispose()
    marks = _marks(spans)
    calls = [s for s in spans if s.kind == "enqueue" and s.t1 > s.t0]
    assert len(calls) == computes
    for call in calls:
        pairs, inside = _launch_pairs(
            [s for s in marks if call.t0 <= s.t0 <= call.t1])
        assert [s.tag for s in inside if s.kind == "engage"] == [
            "part:stage", "part:submit", "part:join", "part:note"]
        done = [s for s in inside if s.tag == "phase-done"]
        assert sorted(s.lane for s in done) == [0, 1]
        assert all(s.kind == "enqueue" and s.cid == 40 for s in done)
        for lane in (0, 1):
            assert [s.tag for s in inside if s.tag in _PHASE
                    and s.lane == lane] == list(_PHASE)
            assert [s.tag for s in pairs if s.lane == lane] == [
                "part:call", "part:handed"]
    assert not [s for s in marks if s.kind == "resync"]


def test_a_ladder_start_is_said_on_the_windows_first_span(devs, monkeypatch):
    """The first ``enqueue`` span of a window that started on the ladder
    carries ``start=ladder`` and keeps the tag's ending ``fused-defer``
    (what tells a deferred compute from a per-call one); the metrics
    registry carries the same counts as fused_stats."""
    from cekirdekler_tpu.metrics.registry import REGISTRY
    from cekirdekler_tpu.trace.spans import TRACER

    def count(how):
        return REGISTRY.counter("ck_fused_window_start_total", how=how).value

    before = {h: count(h) for h in ("ladder", "first-sighting")}
    cr, x, seen = _start_case(devs, monkeypatch)
    try:
        for _ in range(3):
            for _ in range(5):
                x.compute(cr, 38, "inc", 1024, 64)
            cr.barrier()
    finally:
        TRACER.disable()
    assert seen == [("per-call:first-sighting", "inc"),
                    ("ladder", "inc fused-defer"),
                    ("ladder", "inc fused-defer")]
    assert {h: count(h) - n for h, n in before.items()} == {
        "ladder": 2, "first-sighting": 1}
    cr.enqueue_mode = False
    np.testing.assert_array_equal(np.asarray(x), 15.0)
    cr.dispose()


def test_the_ladders_scalars_are_put_on_the_lane_once(devs, monkeypatch):
    """A ladder dispatch hands its run-time scalars (offset, units,
    iterations) over as int32 arrays kept on the lane: a window's seven
    dispatches put each distinct value on the device once, and the
    windows after it none."""
    import jax

    cr = NumberCruncher(devs.subset(1), INC)
    x = ClArray(np.zeros(1024, np.float32), name="x", partial_read=True)
    puts: list = []
    device_put = jax.device_put

    def spy(value, *a, **kw):
        if isinstance(value, np.int32):
            puts.append(int(value))
        return device_put(value, *a, **kw)

    monkeypatch.setattr(jax, "device_put", spy)
    cr.enqueue_mode = True
    for _ in range(3):
        for _ in range(50):
            x.compute(cr, 40, "inc", 1024, 64)
        cr.barrier()
    lane = cr.cores.workers[0]
    # offset 0, 16 units, the iteration counts of x1 x2 x4 x8 x16 and the
    # residues x1 (the first window's) and x3: every value once
    assert sorted(puts) == [0, 1, 2, 3, 4, 8, 16]
    a, b, c = lane.ladder_scalars(0, 16, 3)
    assert lane.ladder_scalars(0, 16, 3) == (a, b, c)  # the same arrays
    assert all(s.dtype == np.int32 and s.devices() == {lane.device}
               for s in (a, b, c)) and int(c) == 3
    cr.enqueue_mode = False
    np.testing.assert_array_equal(np.asarray(x), 150.0)
    cr.dispose()
    assert not lane._ladder_scalars


def test_compute_fused_batch_is_one_dispatch_a_batch(devs):
    """The serving tier's coalesced batch stays ONE ladder dispatch a lane
    whatever the ramp: it counts its iterations in at once and flushes
    itself; a batch that repeats the last window's starts on the ladder
    whole, with no per-call iteration in front."""
    cr = NumberCruncher(devs.subset(1), INC)
    x = ClArray(np.zeros(512, np.float32), name="x", partial_read=True)
    cr.enqueue_mode = True
    first = cr.cores.compute_fused_batch(["inc"], [x], 39, 512, 64, 9)
    assert first["per_call_iters"] == 2 and first["ladder_iters"] == 7
    assert cr.fused_stats["windows"] == 1
    cr.barrier()
    for k in (8, 3):
        w0 = cr.fused_stats["windows"]
        out = cr.cores.compute_fused_batch(["inc"], [x], 39, 512, 64, k)
        assert out["per_call_iters"] == 0 and out["ladder_iters"] == k
        assert cr.fused_stats["windows"] == w0 + 1
        cr.barrier()
    assert cr.fused_stats["window_starts"] == {"first-sighting": 1,
                                               "ladder": 2}
    cr.enqueue_mode = False
    np.testing.assert_array_equal(np.asarray(x), 20.0)
    cr.dispose()


# ---------------------------------------------------------------------------
# the r7 KNOWN LIMIT: multi-threaded windows + sync-point rebalance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fused", [False, True])
def test_threaded_enqueue_windows_no_lost_updates(devs, fused):
    """Regression for the KNOWN LIMIT the r7 trace hammer surfaced (lost
    updates, 10-12/12 arrays at seed): one thread drives barriers + armed
    rebalances (its chip share forced to oscillate) while another thread
    enqueues a different cid through the same Cores.  The armed
    rebalance's flush+reset must be atomic against the other thread's
    in-flight window — exact final values on BOTH arrays, with the fused
    path on and off (off reproduces the seed code shape)."""
    cr = NumberCruncher(devs.subset(2), INC)
    cr.fused_dispatch = fused
    n = 4096
    x = ClArray(np.zeros(n, np.float32), name="x")  # thread B's array
    x.partial_read = True
    y = ClArray(np.zeros(n, np.float32), name="y")  # thread A's array
    y.partial_read = True
    cr.enqueue_mode = True
    w0, w1 = cr.cores.workers
    f0, f1 = w0.fence, w1.fence
    phases = 6
    per_phase_a = 2
    errors: list = []
    b_iters = 0
    stop = threading.Event()

    def thread_a():
        # alternate which chip lags so the armed rebalance MOVES ranges
        # (flush+reset fires on thread A's next compute each phase)
        try:
            for ph in range(phases):
                slow, orig = (w0, f0) if ph % 2 == 0 else (w1, f1)
                slow.fence = laggy(orig, 0.15)
                for _ in range(per_phase_a):
                    y.compute(cr, 71, "inc", n, 64)
                cr.barrier()
                w0.fence, w1.fence = f0, f1
        except Exception as e:  # pragma: no cover - failure path
            errors.append(e)
        finally:
            w0.fence, w1.fence = f0, f1
            stop.set()

    def thread_b():
        nonlocal b_iters
        try:
            while not stop.is_set() and b_iters < 400:
                x.compute(cr, 72, "inc", n, 64)
                b_iters += 1
        except Exception as e:  # pragma: no cover - failure path
            errors.append(e)

    ta = threading.Thread(target=thread_a)
    tb = threading.Thread(target=thread_b)
    ta.start()
    tb.start()
    ta.join(timeout=120.0)
    tb.join(timeout=120.0)
    assert not errors, errors
    cr.enqueue_mode = False
    # +1.0f on small integers is exact in f32: ANY lost iteration (or a
    # lost region update across a range move) is an integer-sized error
    np.testing.assert_array_equal(np.asarray(x), float(b_iters))
    np.testing.assert_array_equal(np.asarray(y), float(phases * per_phase_a))
    cr.dispose()


@pytest.mark.parametrize("sync", ["range-move", "flush"])
def test_no_window_opens_inside_another_threads_read_back(devs, sync,
                                                          monkeypatch):
    """The threaded test above in a form without a clock (it read 352 of
    400 once).  Thread A's read-back of the deferred results closes
    thread B's fused window first; B's next compute repeats that window
    and used to reopen it on the ladder at once, INSIDE A's read-back: the
    new window's records went to A, its launches came after A's copy to
    the host.  After a range move the coverage reset then sent B's next
    per-call compute to upload the host's older copy over them (11 of 13);
    after a ``flush()`` nothing recorded them for the window's own flush
    (8 of 13).  No window opens from the close to the end of the read-back
    (``Window.held``): B's computes go per call, named ``resync``."""
    from cekirdekler_tpu.core.cores import Cores
    from cekirdekler_tpu.core.window import Window

    cr = NumberCruncher(devs.subset(1), INC)
    x = ClArray(np.zeros(1024, np.float32), name="x", partial_read=True)
    cr.enqueue_mode = True

    def inc(k):
        for _ in range(k):
            x.compute(cr, 73, "inc", 1024, 64)

    inc(4)
    cr.barrier()
    inc(4)  # B's window is open: it repeats the last one
    inside, go_on = threading.Event(), threading.Event()

    def pause(real):
        def paused(self, *args, **kwargs):
            inside.set()
            assert go_on.wait(30.0)
            return real(self, *args, **kwargs)
        return paused

    try:
        if sync == "range-move":
            # inside the block: every worker lock is held, nothing reset yet
            monkeypatch.setattr(Cores, "_start_deferred_downloads",
                                pause(Cores._start_deferred_downloads))
            a = threading.Thread(target=cr.cores._flush_and_reset_coverage)
        else:
            # between the close of the window and the take of the records
            monkeypatch.setattr(Window, "take_deferred",
                                pause(Window.take_deferred))
            a = threading.Thread(target=cr.cores.flush)
        a.start()
        assert inside.wait(30.0)
        b = threading.Thread(target=inc, args=(2,))
        b.start()
        # (after a range move B's per-call phase waits for its lane's lock)
        b.join(timeout=0.5 if sync == "range-move" else 30.0)
        go_on.set()
        a.join(timeout=30.0)
        b.join(timeout=30.0)
        assert not a.is_alive() and not b.is_alive()
        monkeypatch.undo()
        assert cr.cores._window.held == 0
        inc(3)
        cr.enqueue_mode = False
        np.testing.assert_array_equal(x.host(), 13.0)
    finally:
        go_on.set()
        cr.dispose()
