"""Reads across lanes: a kernel sequence whose lanes read what their
neighbours wrote (a stencil stepped in time) through ``compute()`` on more
than one lane — the analysis' proved reach, the widened ``partial_read``
upload, and inside an enqueue window the lane-to-lane exchange
(``Exchange.stage``): exact against a float64 reference at every
cell, lane boundaries included, whatever the split.
"""

import os
import re
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

import cekirdekler_tpu as ct
from cekirdekler_tpu import ClArray
from cekirdekler_tpu.core import exchange as ck_exchange
from cekirdekler_tpu.core.cruncher import NumberCruncher
from cekirdekler_tpu.core.worker import Worker
from cekirdekler_tpu.errors import KernelVerifyError
from cekirdekler_tpu.metrics.registry import REGISTRY

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "examples", "wave_equation.py")) as _f:
    WAVE_SRC = re.search(r'WAVE_SRC = """(.*?)"""', _f.read(), re.S).group(1)

W = H = 64
N = W * H
LR = 64
C2 = 0.22


def _cpus(n):
    return ct.all_devices().cpus().subset(n)


def wave_ref(u0, u1, steps, w=W, h=H):
    a = u0.reshape(h, w).astype(np.float64)
    b = u1.reshape(h, w).astype(np.float64)
    for _ in range(steps):
        lap = np.zeros_like(b)
        lap[1:-1, 1:-1] = (b[1:-1, :-2] + b[1:-1, 2:] + b[:-2, 1:-1]
                           + b[2:, 1:-1] - 4.0 * b[1:-1, 1:-1])
        c = 2.0 * b - a + C2 * lap
        c[0, :] = c[-1, :] = 0.0
        c[:, 0] = c[:, -1] = 0.0
        a, b = b, c
    return a.ravel(), b.ravel()


def field(seed=1):
    f = np.random.default_rng(seed).standard_normal((H, W)).astype(np.float32)
    f[0, :] = f[-1, :] = 0.0
    f[:, 0] = f[:, -1] = 0.0
    return f.ravel()


def wave_arrays(f, partial):
    u0 = ClArray(f.copy(), name="u0", partial_read=partial)
    u1 = ClArray(f.copy(), name="u1", partial_read=partial)
    frame = ClArray(N, np.float32, name="frame", read=False)
    return u0, u1, u0.next_param(u1, frame)


def rel_err(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


def step(cr, group, cid=7):
    group.compute(cr, cid, "waveStep rotate", N, LR, values=(W, H, C2))


# three splits of the 64 work-groups, by hand: the boundaries fall inside
# rows, a lane's share shrinks under the reach of its neighbour's neighbour
SPLITS = {
    1: [[1.0]] * 3,
    2: [[0.5, 0.5], [0.25, 0.75], [0.625, 0.375]],
    4: [[0.25] * 4, [0.125, 0.375, 0.25, 0.25],
        [0.40625, 0.015625, 0.28125, 0.296875]],
}


@pytest.mark.parametrize("partial", [True, False], ids=["partial", "full"])
@pytest.mark.parametrize("mode", ["per_call", "window", "moved_windows"])
@pytest.mark.parametrize("lanes", [1, 2, 4])
def test_wave_step_is_exact_whatever_the_split(lanes, mode, partial):
    f = field()
    u0, u1, group = wave_arrays(f, partial)
    cr = NumberCruncher(_cpus(lanes), WAVE_SRC)
    try:
        if mode == "per_call":
            steps = 8
            for _ in range(steps):
                step(cr, group)
        else:
            windows = 3 if mode == "moved_windows" else 1
            steps = 20 * windows
            cr.enqueue_mode = True
            for k in range(windows):
                if mode == "moved_windows":
                    # the ranges moved by hand: the shares fixed anew and
                    # the table forgotten, so the next compute splits again
                    cr.cores.fixed_compute_powers = SPLITS[lanes][k]
                    cr.cores.global_ranges.pop(7, None)
                for _ in range(20):
                    step(cr, group)
                cr.barrier()
            cr.enqueue_mode = False
        a, b = wave_ref(f, f, steps)
        assert rel_err(u0.host(), a) <= 2e-6
        assert rel_err(u1.host(), b) <= 2e-6
        assert cr.number_of_errors_happened == 0
        if mode == "moved_windows" and lanes > 1:
            assert cr.ranges_of(7) == [int(s * N) for s in SPLITS[lanes][2]]
    finally:
        cr.dispose()


TWO_ROWS = """
__kernel void blur(__global float* a, __global float* b, int width) {
    int i = get_global_id(0);
    int n = get_global_size(0);
    if (i < 2 * width || i >= n - 2 * width) {
        b[i] = a[i];
    } else {
        b[i] = 0.5f * a[i] + 0.125f * (a[i - width] + a[i + width]
                                       + a[i - 2 * width] + a[i + 2 * width]);
    }
}
__kernel void back(__global float* a, __global float* b, int width) {
    int i = get_global_id(0);
    a[i] = b[i];
}
"""

LINE = """
__kernel void smooth(__global float* u, __global float* v) {
    int i = get_global_id(0);
    int n = get_global_size(0);
    if (i == 0 || i == n - 1) {
        v[i] = u[i];
    } else {
        v[i] = 0.5f * (u[i - 1] + u[i + 1]);
    }
}
__kernel void keep(__global float* u, __global float* v) {
    int i = get_global_id(0);
    u[i] = v[i];
}
"""


def _two_rows_ref(a, steps):
    a = a.reshape(H, W).astype(np.float64)
    for _ in range(steps):
        b = a.copy()
        b[2:-2] = 0.5 * a[2:-2] + 0.125 * (a[1:-3] + a[3:-1] + a[:-4] + a[4:])
        a = b
    return a.ravel()


def _line_ref(u, steps):
    u = u.astype(np.float64)
    for _ in range(steps):
        v = u.copy()
        v[1:-1] = 0.5 * (u[:-2] + u[2:])
        u = v
    return u


@pytest.mark.parametrize("case", ["two_rows", "line"])
@pytest.mark.parametrize("mode", ["per_call", "window"])
def test_other_reaches(case, mode):
    """A reach of two rows (``2 * width``: wider than a lane's whole share
    on one of the four lanes) and a 1-D neighbour read (a literal reach of
    one element)."""
    data = np.random.default_rng(3).standard_normal(N).astype(np.float32)
    src, seq, values, ref = (
        (TWO_ROWS, "blur back", (W,), _two_rows_ref) if case == "two_rows"
        else (LINE, "smooth keep", (), _line_ref))
    a = ClArray(data.copy(), name="a", partial_read=True)
    b = ClArray(N, np.float32, name="b", read=False)
    cr = NumberCruncher(_cpus(4), src)
    try:
        # lane 1 holds ONE row: its neighbours' reach passes over it
        cr.cores.fixed_compute_powers = [0.375, 0.015625, 0.359375, 0.25]
        cr.enqueue_mode = mode == "window"
        for _ in range(12):
            a.next_param(b).compute(cr, 11, seq, N, LR, values=values)
        if mode == "window":
            cr.barrier()
            cr.enqueue_mode = False
        assert cr.ranges_of(11) == [1536, 64, 1472, 1024]
        assert rel_err(a.host(), ref(data, 12)) <= 2e-6
    finally:
        cr.dispose()


ROAMING = """
__kernel void shift(__global float* a, __global float* b, int n, int by) {
    int i = get_global_id(0);
    b[i] = a[(i + by) % n];
}
__kernel void back(__global float* a, __global float* b, int n, int by) {
    int i = get_global_id(0);
    a[i] = b[i];
}
"""


def test_a_read_that_cannot_be_bounded_is_still_refused(monkeypatch):
    """``a[(i + by) % n]`` of an array another kernel of the window stores
    to: no reach to exchange.  Strict verification raises, as before."""
    monkeypatch.setenv("CK_KERNEL_VERIFY", "strict")
    a = ClArray(np.arange(N, dtype=np.float32), name="a")
    b = ClArray(N, np.float32, name="b", read=False)
    cr = NumberCruncher(_cpus(2), ROAMING)
    try:
        cr.enqueue_mode = True
        with pytest.raises(KernelVerifyError) as ei:
            a.next_param(b).compute(cr, 12, "shift back", N, LR,
                                    values=(N, 5))
        assert ei.value.finding.kind == "window-raw"
        cr.enqueue_mode = False
        # and the wave step passes the same gate
        f = field()
        u0, u1, group = wave_arrays(f, True)
        wave = NumberCruncher(_cpus(2), WAVE_SRC)
        try:
            wave.enqueue_mode = True
            step(wave, group)
            wave.enqueue_mode = False
        finally:
            wave.dispose()
        assert rel_err(u1.host(), wave_ref(f, f, 1)[1]) <= 2e-6
    finally:
        cr.dispose()


def test_an_on_device_repeat_keeps_the_error(monkeypatch):
    """``repeat_count`` runs the passes on the device with no host between
    them: nothing can be exchanged, the cyclic hazard stays an error."""
    monkeypatch.setenv("CK_KERNEL_VERIFY", "strict")
    u0, u1, group = wave_arrays(field(), False)
    cr = NumberCruncher(_cpus(2), WAVE_SRC)
    try:
        cr.repeat_count = 3
        with pytest.raises(KernelVerifyError) as ei:
            step(cr, group)
        assert ei.value.finding.kind == "window-raw"
    finally:
        cr.dispose()


INC = """
__kernel void inc(__global float* x) {
    int i = get_global_id(0);
    x[i] = x[i] + 1.0f;
}
"""


def test_two_host_threads_one_of_them_exchanging():
    """Two compute ids driven by two host threads through ONE Cores: the
    wave step exchanging on four lanes, a fused increment beside it."""
    f = field()
    u0, u1, group = wave_arrays(f, True)
    x = ClArray(np.zeros(N, np.float32), name="x", partial_read=True)
    cr = NumberCruncher(_cpus(4), WAVE_SRC + INC)
    windows, per = 4, 10
    errs = []
    sync = threading.Barrier(2)

    def drive(fn):
        try:
            for _ in range(windows):
                for _ in range(per):
                    fn()
                sync.wait(timeout=120)
                if fn is wave:
                    cr.barrier()
                sync.wait(timeout=120)
        except Exception as e:  # noqa: BLE001 - surfaced below
            errs.append(e)
            sync.abort()

    def wave():
        step(cr, group, cid=21)

    def inc():
        x.compute(cr, 22, "inc", N, LR)

    try:
        cr.enqueue_mode = True
        threads = [threading.Thread(target=drive, args=(fn,))
                   for fn in (wave, inc)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not errs, errs
        cr.enqueue_mode = False
        a, b = wave_ref(f, f, windows * per)
        assert rel_err(u1.host(), b) <= 2e-6
        assert rel_err(u0.host(), a) <= 2e-6
        np.testing.assert_array_equal(x.host(), float(windows * per))
    finally:
        cr.dispose()


JACOBI = """
__kernel void avg(__global float* a, __global float* b, int width, int height) {
    int i = get_global_id(0);
    int x = i % width;
    int y = i / width;
    if (x == 0 || x == width - 1 || y == 0 || y == height - 1) {
        b[i] = a[i];
    } else {
        b[i] = 0.25f * (a[i - 1] + a[i + 1] + a[i - width] + a[i + width]);
    }
}
__kernel void copy(__global float* a, __global float* b, int width, int height) {
    int i = get_global_id(0);
    a[i] = b[i];
}
"""


def test_a_late_lane_does_not_upload_its_neighbours_next_step(monkeypatch):
    """The Jacobi pair of ISSUE 32 (``b = avg(a)`` / ``a = b``, default
    flags, four lanes, SYNCHRONOUS computes): a lane's phase ends by
    writing its rows of ``a`` into the host array from which the other
    lanes' phases upload theirs; a lane that came late (a compile, a busy
    core) uploaded neighbour rows that were already one step ahead: 7.7e-3
    to 7.6e-2 wrong within a few rows of a lane boundary, now and then.
    Here lane 1 IS late, every call.  The host reads of such a compute are
    staged before any phase starts (``Exchange.stage``)."""
    data = np.random.default_rng(5).standard_normal(N).astype(np.float32)
    a = ClArray(data.copy(), name="a")
    b = ClArray(N, np.float32, name="b")
    real = Worker._h2d

    def late(self, host_slice, zero_copy):
        if self.index == 1:
            time.sleep(0.05)
        return real(self, host_slice, zero_copy)

    monkeypatch.setattr(Worker, "_h2d", late)
    cr = NumberCruncher(_cpus(4), JACOBI)
    try:
        for _ in range(4):
            a.next_param(b).compute(cr, 13, "avg copy", N, LR,
                                    values=(W, H))
        want = data.reshape(H, W).astype(np.float64)
        for _ in range(4):
            nxt = want.copy()
            nxt[1:-1, 1:-1] = 0.25 * (want[1:-1, :-2] + want[1:-1, 2:]
                                      + want[:-2, 1:-1] + want[2:, 1:-1])
            want = nxt
        assert rel_err(a.host(), want.ravel()) <= 2e-6
    finally:
        cr.dispose()


@pytest.fixture(scope="module")
def traced_window(tmp_path_factory):
    """One enqueue window of 20 wave steps on four lanes under a
    ``jax.profiler`` session: the ``ck/`` events of the host plane, and the
    exchange's counters before and after."""
    import jax
    from jax.profiler import ProfileData

    from cekirdekler_tpu.trace.spans import TRACER

    f = field()
    u0, u1, group = wave_arrays(f, True)
    cr = NumberCruncher(_cpus(4), WAVE_SRC)
    trace_dir = str(tmp_path_factory.mktemp("halo"))

    def counters():
        snap = REGISTRY.snapshot()["counters"]
        return {k: v for k, v in snap.items() if k.startswith("ck_halo_")}

    before = counters()
    TRACER.disable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        cr.enqueue_mode = True
        for _ in range(20):
            step(cr, group)
        cr.barrier()
        cr.enqueue_mode = False
    finally:
        jax.profiler.stop_trace()
    after = counters()
    stats = dict(cr.cores.fused_stats["disengaged"])
    cr.dispose()
    path = [os.path.join(r, name) for r, _d, names in os.walk(trace_dir)
            for name in names if name.endswith(".xplane.pb")][0]
    events = [SimpleNamespace(name=ev.name, stats=dict(ev.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events
              if ev.name.startswith("ck/")]
    a, b = wave_ref(f, f, 20)
    assert rel_err(u1.host(), b) <= 2e-6
    return SimpleNamespace(events=events, before=before, after=after,
                           disengaged=stats)


def test_the_window_holds_its_halo_spans(traced_window):
    halos = [e for e in traced_window.events if e.name == "ck/halo"]
    # the first compute uploads its reach from the host; 19 exchange
    assert len(halos) == 19 * 4
    strip = W * 4  # one row of float32: the reach is ``width`` elements
    for e in halos:
        lane = int(e.stats["lane"])
        inner = lane in (1, 2)
        assert int(e.stats["bytes"]) == (2 if inner else 1) * strip
        assert e.stats["tag"] == "d2d"
        assert str(e.stats["src"]) == {
            0: "1", 1: "0+2", 2: "1+3", 3: "2"}[lane]
        assert int(e.stats["cid"]) == 7 and "win" in e.stats
    wins = {e.stats["win"] for e in halos}
    assert len(wins) == 1


def test_the_window_says_once_why_it_does_not_fuse(traced_window):
    instants = [e for e in traced_window.events if e.name == "ck/fused"
                and str(e.stats.get("tag", "")).startswith("disengage:")]
    assert [e.stats["tag"] for e in instants] == ["disengage:halo"]
    assert traced_window.disengaged == {"halo": 1}
    # and no compute of it was deferred into a ladder
    enq = [e for e in traced_window.events if e.name == "ck/enqueue"]
    # (each compute's four lanes say ``phase-start``, ``phase-locked`` and
    # ``phase-done``: instants of the kind)
    phases = {tag: [e for e in enq if e.stats.get("tag") == tag]
              for tag in ("phase-start", "phase-locked", "phase-done")}
    for said in phases.values():
        assert len(said) == 20 * 4 and all("lane" in e.stats for e in said)
    assert all("hop_us" in e.stats and "queued_us" not in e.stats
               for e in phases["phase-start"])
    enq = [e for e in enq if not str(e.stats.get("tag")).startswith("phase-")]
    assert len(enq) == 20
    # every compute of it cut its strips on the caller's thread first
    stages = [e for e in traced_window.events if e.name == "ck/engage"
              and e.stats.get("tag") == "part:stage"]
    assert len(stages) == 20
    assert not any("fused-defer" in str(e.stats.get("tag")) for e in enq)
    assert not any(e.name == "ck/resync" and e.stats.get("tag") == "range-move"
                   for e in traced_window.events)


def test_launch_spans_carry_the_reach(traced_window):
    launches = [e for e in traced_window.events if e.name == "ck/launch"]
    assert launches and all(e.stats.get("reach") == f"u1:{W}"
                            for e in launches)


def test_the_exchange_is_counted_by_lane(traced_window):
    def delta(name, lane):
        key = [k for k in traced_window.after
               if k.startswith(name) and f'lane="{lane}"' in k]
        assert len(key) == 1, (name, lane, list(traced_window.after))
        return (traced_window.after[key[0]]
                - traced_window.before.get(key[0], 0))

    for lane in range(4):
        strips = 2 if lane in (1, 2) else 1
        assert delta("ck_halo_exchanges_total", lane) == 19 * strips
        assert delta("ck_halo_bytes_total", lane) == 19 * strips * W * 4


def test_owner_intervals():
    split, assign = ck_exchange._own_split, ck_exchange._own_assign
    owned = assign(assign((), 0, 100, 0), 100, 200, 1)
    assert owned == [(0, 100, 0), (100, 200, 1)]
    assert split(owned, 90, 210) == [(90, 100, 0), (100, 200, 1),
                                     (200, 210, None)]
    owned = assign(owned, 80, 120, 2)
    assert owned == [(0, 80, 0), (80, 120, 2), (120, 200, 1)]
    assert assign(owned, 80, 120, 0)[0] == (0, 120, 0)
    assert split((), 5, 9) == [(5, 9, None)]
    assert split(owned, 300, 310) == [(300, 310, None)]
    assert ck_exchange._strip_sizes(64 * 13 + 5, 64) == [512, 256, 64, 5]
    assert ck_exchange._strip_sizes(3, 64) == [3]
