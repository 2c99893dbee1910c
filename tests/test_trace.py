"""The span-based attribution subsystem (cekirdekler_tpu/trace/):
overhead budget, ring-buffer semantics, spans from every runtime layer,
per-cid fence splitting on a skewed two-kernel window, Chrome-trace
schema round-trip, and the per-rep overlap ceiling's structural bounds.
"""

import json
import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

import cekirdekler_tpu as ct
from cekirdekler_tpu.arrays.clarray import ClArray
from cekirdekler_tpu.core.cruncher import NumberCruncher
from cekirdekler_tpu.trace import (
    TRACER,
    RepSample,
    Span,
    Tracer,
    ceiling_report,
    from_chrome_trace,
    rep_ceiling,
    split_fence_benches,
    to_chrome_trace,
    tracing,
    window_report,
)

SAXPY = """
__kernel void saxpy(__global float* x, __global float* y, float a) {
    int i = get_global_id(0);
    y[i] = y[i] + a * x[i];
}
"""

TWO_KERNELS = """
__kernel void heavy(__global float* x, __global float* y) {
    int i = get_global_id(0);
    float acc = x[i];
    for (int k = 0; k < 40000; k++) { acc = acc + x[i] * 0.25f; }
    y[i] = acc;
}
__kernel void light(__global float* x, __global float* y) {
    int i = get_global_id(0);
    y[i] = x[i] + 1.0f;
}
"""


def _cpus(k=2):
    return ct.platforms().cpus().subset(k)


@pytest.fixture(autouse=True)
def _tracer_off():
    """Every test starts and ends with the global tracer disabled — a
    test that leaks an enabled tracer would tax the whole suite."""
    TRACER.disable()
    TRACER.clear()
    yield
    TRACER.disable()
    TRACER.clear()


# -- overhead budget ---------------------------------------------------------

def test_disabled_tracer_overhead_under_budget():
    """The ISSUE's stated budget: an inactive tracer's would-be span costs
    < 1 µs — the two-way test (ring off, no profiler session running)
    included.  Measured over 50k t0()/record() pairs (the hot-site
    convention), best of 3 runs to shrug off scheduler noise."""
    import jax

    tr = Tracer()
    assert not tr.enabled and not tr.active()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    n = 50_000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            t = tr.t0("launch")
            tr.record("launch", t, cid=1, lane=0)
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 1e-6, f"disabled span cost {best*1e9:.0f} ns >= 1 µs"
    assert tr.total_recorded == 0  # truly a no-op: nothing stored
    # ... and no annotation opened: the token is the inactive sentinel,
    # and the probe is the profiler's own static method, bound on first use
    assert tr.t0("launch") == 0.0
    assert tr._session_on is jax.profiler.TraceAnnotation.is_enabled
    assert tr.bind(len) is len  # nothing wraps a closure either


def test_enabled_tracer_records_and_costs_sanely():
    tr = Tracer(capacity=1024)
    tr.enable()
    n = 1000
    t0 = time.perf_counter()
    for i in range(n):
        t = tr.t0()
        tr.record("launch", t, cid=i, lane=0, tag="x")
    per = (time.perf_counter() - t0) / n
    assert tr.total_recorded == n
    assert per < 5e-5  # sanity only; the hard budget is the disabled path


# -- ring buffer -------------------------------------------------------------

def test_ring_buffer_wraps_keeping_newest():
    tr = Tracer(capacity=16)
    tr.enable()
    for i in range(40):
        tr.instant("launch", cid=i)
    spans = tr.snapshot()
    assert len(spans) == 16
    assert tr.total_recorded == 40
    assert sorted(s.cid for s in spans) == list(range(24, 40))


def test_record_ignores_disabled_open():
    """A span opened while disabled must not record even if the tracer
    was enabled mid-span (t0 == 0.0 sentinel)."""
    tr = Tracer()
    t = tr.t0()
    tr.enable()
    tr.record("launch", t)
    assert tr.total_recorded == 0


def test_tracing_scope_disables_on_exit():
    with tracing() as tr:
        assert tr.enabled
        tr.instant("split")
    assert not TRACER.enabled
    assert len(TRACER.snapshot()) == 1  # spans survive the scope


# -- spans from the runtime layers ------------------------------------------

def test_spans_from_worker_cores_and_both_engines():
    from cekirdekler_tpu.core.cores import PIPELINE_DRIVER, PIPELINE_EVENT

    n = 1024
    x = ClArray(np.arange(n, dtype=np.float32), partial_read=True,
                read_only=True)
    y = ClArray(np.ones(n, np.float32), partial_read=True)
    cr = NumberCruncher(_cpus(2), SAXPY)
    try:
        with tracing() as tr:
            t0 = time.perf_counter()
            g = x.next_param(y)
            g.compute(cr, 11, "saxpy", n, 64, values=(2.0,))
            g.compute(cr, 11, "saxpy", n, 64, pipeline=True,
                      pipeline_blobs=4, pipeline_type=PIPELINE_EVENT,
                      values=(2.0,))
            g.compute(cr, 11, "saxpy", n, 64, pipeline=True,
                      pipeline_blobs=4, pipeline_type=PIPELINE_DRIVER,
                      values=(2.0,))
            cr.barrier()
            t1 = time.perf_counter()
        spans = tr.snapshot()
        kinds = {s.kind for s in spans}
        # worker layer
        assert {"upload", "launch", "download", "fence"} <= kinds
        # cores layer: the compute() entry + the first range split
        assert {"enqueue", "split"} <= kinds
        # both pipeline engines emitted their engine spans
        engine_tags = {s.tag.split()[0] for s in spans
                       if s.kind == "pipeline-stage" and s.tag}
        assert {"EVENT", "DRIVER"} <= engine_tags
        # cid threading: every launch span carries the compute id
        launches = [s for s in spans if s.kind == "launch"]
        assert launches and all(s.cid == 11 for s in launches)
        assert all(s.lane in (0, 1) for s in launches)
        # the window report reconciles: coverage cannot exceed wall
        rep = window_report(spans, t0, t1)
        assert 0 <= rep.covered_ms <= rep.wall_ms + 1e-6
        assert rep.gap_ms >= 0
        assert rep.per_cid[11]["launch"] > 0
    finally:
        cr.dispose()


def test_spans_from_device_pipeline_and_pool():
    from cekirdekler_tpu.pipeline.device_pipeline import ClPipeline, PipelineStage
    from cekirdekler_tpu.pipeline.pool import ClDevicePool, ClTask, ClTaskPool

    n = 256
    with tracing() as tr:
        # device pipeline stage spans
        st1 = PipelineStage(SAXPY, "saxpy", n, 64, values=(1.0,))
        st1.add_input(np.arange(n, dtype=np.float32))
        st1.add_output(np.zeros(n, np.float32))
        st2 = PipelineStage(SAXPY, "saxpy", n, 64, values=(1.0,))
        st2.add_input(np.zeros(n, np.float32))
        st2.add_output(np.zeros(n, np.float32))
        pipe = ClPipeline.make([st1, st2], list(_cpus(2)))
        try:
            pipe.push([np.arange(n, dtype=np.float32)])
            pipe.push([np.arange(n, dtype=np.float32)])
        finally:
            pipe.dispose()
        stage_spans = [s for s in tr.snapshot() if s.kind == "pipeline-stage"]
        assert len(stage_spans) >= 4  # 2 stages x 2 pushes

        # pool task spans
        x = ClArray(np.arange(n, dtype=np.float32), read_only=True)
        y = ClArray(np.zeros(n, np.float32))
        pool = ClTaskPool()
        for _ in range(3):
            pool.add(ClTask(params=[x, y], kernel_names=["saxpy"],
                            compute_id=5, global_range=n, local_range=64,
                            values=(1.0,)))
        with ClDevicePool(_cpus(2), SAXPY) as dp:
            dp.enqueue_task_pool(pool)
            dp.finish()
        pool_spans = [s for s in tr.snapshot() if s.kind == "pool-task"]
        assert len(pool_spans) == 3
        assert all(s.cid == 5 for s in pool_spans)


# -- fence split -------------------------------------------------------------

def test_split_fence_benches_marginals():
    t0 = 100.0
    comps = [(1, 100.010), (2, 100.011), (3, 100.050)]
    b = split_fence_benches(comps, t0)
    assert b[1] == pytest.approx(10.0, abs=1e-6)
    assert b[2] == pytest.approx(1.0, abs=1e-6)
    assert b[3] == pytest.approx(39.0, abs=1e-6)
    # out-of-order clock jitter clamps at 0, never negative
    b2 = split_fence_benches([(1, 100.010), (2, 100.009)], t0)
    assert b2[2] == 0.0


def test_fence_split_attributes_skewed_two_kernel_window():
    """The VERDICT r5 #8 distortion, measured and closed: a mixed
    enqueue window of a heavy and a light kernel.  Without the split
    both compute ids inherit the whole-window fence time (the documented
    approximation); with ``fence_split`` the light kernel's bench must
    come out a small fraction of the heavy one's."""
    n = 8192
    x = ClArray(np.arange(n, dtype=np.float32) % 7, partial_read=True,
                read_only=True)
    yh = ClArray(n, np.float32, name="tyh", partial_read=True)
    yl = ClArray(n, np.float32, name="tyl", partial_read=True)

    def window(split: bool):
        cr = NumberCruncher(_cpus(2), TWO_KERNELS)
        try:
            cr.fence_split = split
            cr.enqueue_mode = True
            for _ in range(3):
                x.next_param(yh).compute(cr, 31, "heavy", n, 256)
            for _ in range(3):
                x.next_param(yl).compute(cr, 32, "light", n, 256)
            cr.barrier()
            heavy = cr.benchmarks_of(31)
            light = cr.benchmarks_of(32)
            cr.enqueue_mode = False
            return heavy, light
        finally:
            if cr.enqueue_mode:
                cr.enqueue_mode = False
            cr.dispose()

    heavy0, light0 = window(split=False)
    # the documented default: one fence time for every id in the window
    assert heavy0 == light0
    heavy1, light1 = window(split=True)
    for h, l in zip(heavy1, light1):
        assert h > 0 and l >= 0
        # the skew is ~1000x on this kernel pair; 5x is a safe floor
        # that still fails hard if the split regresses to whole-window
        assert l < h / 5.0, (heavy1, light1)
    # correctness survives the split path (flush after the barrier)
    np.testing.assert_allclose(
        np.asarray(yl.host()), np.asarray(x.host()) + 1.0
    )


def test_fence_split_correct_results_and_rebalance_arming():
    """The split path must leave the sync-point rebalance machinery
    working: ids still arm, ranges still move on the next call."""
    n = 4096
    x = ClArray(np.arange(n, dtype=np.float32), partial_read=True,
                read_only=True)
    y = ClArray(np.ones(n, np.float32), partial_read=True)
    cr = NumberCruncher(_cpus(2), SAXPY)
    try:
        cr.fence_split = True
        cr.enqueue_mode = True
        for _ in range(4):
            x.next_param(y).compute(cr, 41, "saxpy", n, 64, values=(1.0,))
        cr.barrier()
        assert 41 in cr.cores._window.rebalance
        x.next_param(y).compute(cr, 41, "saxpy", n, 64, values=(1.0,))
        cr.enqueue_mode = False
        np.testing.assert_allclose(
            np.asarray(y.host()),
            1.0 + 5.0 * np.arange(n, dtype=np.float32),
        )
    finally:
        if cr.enqueue_mode:
            cr.enqueue_mode = False
        cr.dispose()


# -- chrome export -----------------------------------------------------------

def test_chrome_trace_roundtrip_schema():
    base = time.perf_counter()
    spans = [
        Span("launch", base, base + 0.005, cid=7, lane=0, tag="k1 x2"),
        Span("upload", base + 0.001, base + 0.002, cid=7, lane=1, tag="a"),
        Span("fence", base + 0.006, base + 0.009, cid=None, lane=None,
             tag="barrier"),
    ]
    trace = to_chrome_trace(spans)
    # schema facts chrome://tracing / Perfetto rely on
    blob = json.dumps(trace)
    parsed = json.loads(blob)
    evs = parsed["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == len(spans)
    for e in xs:
        assert {"name", "pid", "tid", "ts", "dur"} <= set(e)
        assert e["ts"] >= 0 and e["dur"] >= 0
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"
             and e["name"] == "thread_name"}
    assert {"host", "lane 0", "lane 1"} <= names
    # round trip: kinds, cids, lanes, tags, durations survive
    back = from_chrome_trace(parsed)
    assert len(back) == len(spans)
    orig = sorted(spans, key=lambda s: s.t0)
    for a, b in zip(orig, back):
        assert a.kind == b.kind and a.cid == b.cid and a.lane == b.lane
        assert a.tag == b.tag
        assert b.dur_ms == pytest.approx(a.dur_ms, rel=1e-6)


# -- overlap ceiling ---------------------------------------------------------

def test_rep_ceiling_witness_clamp_and_bounds():
    # good engine: achieved lands near the model's prediction
    s = RepSample(r=10.0, c=30.0, w=10.0, p=33.0, h2d=10.0, d2h=10.0,
                  dup=12.0)
    r = rep_ceiling(s, blobs=8)
    assert r["achieved_vs_ceiling"] is not None
    assert 0.9 <= r["achieved_vs_ceiling"] <= 1.0
    # engine beats the model (the r5 1.15 case): ratio saturates at 1.0,
    # flagged — never above
    s2 = RepSample(r=10.0, c=30.0, w=10.0, p=29.0, h2d=10.0, d2h=10.0,
                   dup=20.0)
    r2 = rep_ceiling(s2, blobs=8)
    assert r2["model_beaten"]
    assert r2["achieved_vs_ceiling"] == pytest.approx(1.0)
    # poor engine: honestly below — no clipping upward
    s3 = RepSample(r=10.0, c=30.0, w=10.0, p=48.0, h2d=10.0, d2h=10.0,
                   dup=12.0)
    r3 = rep_ceiling(s3, blobs=8)
    assert r3["achieved_vs_ceiling"] < 0.9


def test_rep_ceiling_ratio_in_unit_interval_under_noise():
    """Property sweep: whatever the (noisy) inputs, the per-rep ratio is
    a [0, 1] fraction — the structural guarantee that fixes the
    broken-ruler finding (negative-overlap reps floor at 0 and are
    counted by ceiling_report, never fed raw into the median)."""
    rng = np.random.default_rng(0)
    for _ in range(500):
        vals = rng.uniform(0.1, 50.0, size=7)
        s = RepSample(*[float(v) for v in vals])
        r = rep_ceiling(s, blobs=int(rng.integers(2, 17)))
        if r["achieved_vs_ceiling"] is not None:
            assert 0.0 <= r["achieved_vs_ceiling"] <= 1.0 + 1e-9


def test_ceiling_report_counts_negative_overlap_reps():
    # p > serial: pipelining ran SLOWER than serial — achieved < 0
    bad = RepSample(r=1.0, c=5.0, w=1.0, p=8.0, h2d=1.0, d2h=1.0, dup=1.2)
    rep = ceiling_report([bad], blobs=4)
    assert rep["negative_overlap_reps"] == 1
    assert rep["achieved_vs_ceiling"] == 0.0  # floored, not negative


def test_ceiling_report_medians_and_spread():
    reps = [
        RepSample(r=10, c=30, w=10, p=33, h2d=10, d2h=10, dup=12),
        RepSample(r=11, c=31, w=9, p=32, h2d=10, d2h=10, dup=13),
        RepSample(r=9, c=29, w=11, p=34, h2d=10, d2h=10, dup=11),
    ]
    rep = ceiling_report(reps, blobs=8)
    assert rep["n_reps"] == 3
    assert len(rep["per_rep_achieved_vs_ceiling"]) == 3
    assert rep["achieved_vs_ceiling"] <= 1.0
    assert rep["achieved_vs_ceiling_spread"] >= 0.0
    assert 0.9 <= rep["achieved_vs_ceiling"] <= 1.0


# -- the profiler bridge: the program's spans on the profiler's clock --------

INC = """
__kernel void inc(__global float* x) {
    int i = get_global_id(0);
    x[i] = x[i] + 1.0f;
}
"""


def _laggy(orig, secs=0.2):
    def f():
        time.sleep(secs)
        orig()

    return f


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """ONE ``jax.profiler`` session on the CPU backend with the ring OFF:
    enqueue windows of a fresh kernel on two virtual lanes (the first cold,
    one after a lagging lane so that the ranges move), MARKS on.  Returns
    the ``ck/`` / ``test/`` events of the dump's host plane."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    from cekirdekler_tpu.trace.device import MARKS

    n, calls = 4096, 4
    cr = NumberCruncher(_cpus(2), INC)
    x = ClArray(np.zeros(n, np.float32), name="bridge_x", partial_read=True)
    trace_dir = str(tmp_path_factory.mktemp("bridge"))

    def window():
        for _ in range(calls):
            x.compute(cr, 77, "inc", n, 64)
        cr.barrier()

    TRACER.disable()
    MARKS.enable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        assert TRACER.active() and not TRACER.enabled
        cr.enqueue_mode = True
        with TraceAnnotation("test/cold"):
            window()
        with TraceAnnotation("test/warm"):
            window()
        slow = cr.cores.workers[0]
        fence = slow.fence
        slow.fence = _laggy(fence)
        window()  # lane 0 lags at the barrier: the next call re-splits
        slow.fence = fence
        before = cr.ranges_of(77)
        with TraceAnnotation("test/moved"):
            window()
        moved = cr.ranges_of(77) != before
        cr.enqueue_mode = False
    finally:
        jax.profiler.stop_trace()
        marks = MARKS.snapshot()
        MARKS.disable()
    np.testing.assert_array_equal(np.asarray(x), 4.0 * calls)
    ring = TRACER.total_recorded
    cr.dispose()
    path = [os.path.join(r, f) for r, _d, fs in os.walk(trace_dir)
            for f in fs if f.endswith(".xplane.pb")][0]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(("ck/", "test/", "ck|")):
                    events.append(SimpleNamespace(
                        name=ev.name, t0=ev.start_ns,
                        t1=ev.start_ns + ev.duration_ns, line=li,
                        stats=dict(ev.stats)))
    return SimpleNamespace(events=events, marks=marks, ring=ring,
                           moved=moved, calls=calls)


def _is_mark(tag) -> bool:
    """A part mark or an anchor (trace/spans.py, "Part marks"): an instant
    inside a span, no span of its own."""
    tag = str(tag or "")
    return tag.startswith("part:") or tag in (
        "retired", "phase-start", "phase-locked", "phase-done")


def _inside(p, outer_name, kind, marks=False):
    """The ``ck/<kind>`` spans inside one of the test's own annotations, or
    with ``marks`` the instants that cut them."""
    (outer,) = [e for e in p.events if e.name == outer_name]
    return [e for e in p.events if e.name == "ck/" + kind
            and outer.t0 <= e.t0 and e.t1 <= outer.t1
            and _is_mark(e.stats.get("tag")) == marks], outer


def test_profiler_session_alone_activates_the_tracer(profiled):
    assert profiled.ring == 0  # the ring stayed off: nothing recorded there
    assert any(e.name == "ck/enqueue" for e in profiled.events)
    # a closed session switches the sites off again
    assert not TRACER.active()


@pytest.mark.parametrize("kind", [
    "enqueue", "schedule", "engage", "fused", "drain", "launch", "fence"])
def test_window_yields_span_on_the_profilers_clock(profiled, kind):
    """One enqueue window under a profiler session: every stretch of
    compute() / barrier() has its ``ck/<kind>`` annotation in the dump's
    host plane, nested inside the test's own, carrying win (and cid)."""
    spans, outer = _inside(profiled, "test/warm", kind)
    assert spans, f"no ck/{kind} inside the test's own annotation"
    caller = [e for e in spans if e.line == outer.line]
    wins = {e.stats.get("win") for e in spans}
    assert len(wins) == 1 and None not in wins  # one window, one id
    if kind not in ("drain", "fence"):
        assert all(e.stats.get("cid") == 77 for e in spans)
    if kind == "launch":
        # the fused ladder launches run on the per-lane driver threads
        driver = [e for e in spans if e.line != outer.line]
        assert {e.stats["lane"] for e in driver} == {0, 1}
        assert all(e.stats["queued_us"] >= 0.0 for e in driver)
        assert any(str(e.stats["tag"]).startswith("fused:inc x")
                   for e in driver)
    else:
        assert caller and "queued_us" not in caller[0].stats
    if kind == "enqueue":
        # the per-call path once, then deferrals into the fused window
        tags = [str(e.stats["tag"]) for e in caller]
        assert sum(not t.endswith("fused-defer") for t in tags) >= 1
        assert sum(t.endswith("fused-defer") for t in tags) >= 1
        assert len(tags) == profiled.calls


def test_window_ids_differ_between_windows(profiled):
    cold, _ = _inside(profiled, "test/cold", "fence")
    warm, _ = _inside(profiled, "test/warm", "fence")
    assert cold[0].stats["win"] != warm[0].stats["win"]


def test_forced_range_move_yields_resync(profiled):
    assert profiled.moved, "the lagging lane did not move the ranges"
    resync, _ = _inside(profiled, "test/moved", "resync")
    assert [str(e.stats["tag"]) for e in resync] == ["range-move"]
    rebalance, _ = _inside(profiled, "test/moved", "rebalance")
    assert len(rebalance) == 1 and rebalance[0].t1 - rebalance[0].t0 < 1e6
    # the deferred results come back inside it, with the bytes staged
    down, _ = _inside(profiled, "test/moved", "download")
    assert down and all(resync[0].t0 <= e.t0 and e.t1 <= resync[0].t1
                        for e in down)
    assert sum(e.stats["bytes"] for e in down) == 4096 * 4
    # a window whose ranges held still has none
    assert _inside(profiled, "test/warm", "resync")[0] == []


def _marks_in(p, span, kind):
    """Tags of the ``ck/<kind>`` marks that lie inside ``span``, in time
    order, with the events themselves."""
    got = sorted((e for e in p.events if e.name == "ck/" + kind
                  and _is_mark(e.stats.get("tag"))
                  and span.t0 <= e.t0 and e.t1 <= span.t1),
                 key=lambda e: e.t0)
    return [str(e.stats["tag"]) for e in got], got


def test_barrier_marks_its_parts_and_each_lane_its_retirement(profiled):
    """Two lanes: ``wait``, one ``retired`` a lane on the thread that
    waited for it, ``feed``, ``close``, in that order inside the barrier's
    ``ck/fence``; an instant carries the window and no ``queued_us``."""
    (barrier,), outer = _inside(profiled, "test/warm", "fence")
    assert str(barrier.stats["tag"]) == "barrier"
    tags, marks = _marks_in(profiled, barrier, "fence")
    assert tags == ["part:wait", "retired", "retired", "part:feed",
                    "part:close"]
    retired = [e for e in marks if e.stats["tag"] == "retired"]
    assert {e.stats["lane"] for e in retired} == {0, 1}
    assert all(e.line != outer.line for e in retired)  # the pool's threads
    assert all(e.line == outer.line for e in marks if e not in retired)
    assert all(e.stats["win"] == barrier.stats["win"]
               and "queued_us" not in e.stats for e in marks)
    assert all(e.t1 - e.t0 < 1e6 for e in marks)  # instants: under a ms


def test_range_move_marks_the_parts_of_its_resync(profiled):
    (resync,), _ = _inside(profiled, "test/moved", "resync")
    tags, marks = _marks_in(profiled, resync, "resync")
    assert tags == ["part:locks", "part:issue", "part:lane", "part:lane",
                    "part:join", "part:reset"]
    # one mark a lane where the issue ends (ISSUE 42): what the lane was
    # handed, by ONE dispatch whatever its share
    lanes = marks[2:4]
    assert [e.stats["lane"] for e in lanes] == [0, 1]
    assert all(e.stats["dispatches"] == 1 and e.stats["pieces"] == 1
               and e.stats["bytes"] > 0 and e.stats["issue_us"] >= 0
               and e.line == resync.line for e in lanes)
    # the waits keep their own spans, inside ``join``
    down, _ = _inside(profiled, "test/moved", "download")
    join, reset = marks[4], marks[5]
    assert down and all(join.t0 <= e.t0 and e.t1 <= reset.t0 for e in down)


def test_range_moves_downloads_mark_issued_and_landed(profiled):
    """Every download of the resync: ``part:issued`` (the copy to the host
    is on its way) before the span opens, ``part:landed`` inside it, both on
    the span's own kind with its lane, its bytes and the array's name."""
    for kind in ("download", "download-chunk"):
        spans, _ = _inside(profiled, "test/moved", kind)
        marks, _ = _inside(profiled, "test/moved", kind, marks=True)
        tags = [str(e.stats["tag"]) for e in marks]
        assert tags.count("part:issued") == tags.count("part:landed") == len(
            spans), (kind, tags)
        for span in spans:
            (landed,) = [e for e in marks if e.stats["tag"] == "part:landed"
                         and e.line == span.line
                         and span.t0 <= e.t0 and e.t1 <= span.t1]
            (issued,) = [e for e in marks if e.stats["tag"] == "part:issued"
                         and (e.stats["lane"], e.stats["off"]) == (
                             landed.stats["lane"], landed.stats["off"])]
            assert issued.t1 <= span.t0 and issued.t0 < landed.t0
            assert (issued.stats["bytes"] == landed.stats["bytes"]
                    == span.stats["bytes"])
            assert issued.stats["name"] == landed.stats["name"] == "bridge_x"
            assert landed.stats["lane"] == span.stats["lane"]
            assert "queued_us" not in landed.stats
    # the window's downloads are one kind or the other, and there are some
    assert _inside(profiled, "test/moved", "download")[0] or _inside(
        profiled, "test/moved", "download-chunk")[0]


@pytest.mark.parametrize("chunks", [1, 4])
def test_a_synchronous_computes_read_back_is_marked_and_timed(chunks):
    """A non-windowed ``compute()`` on one lane, monolithic and in four
    streamed chunks: with the ring on, one ``part:issued`` and one
    ``part:landed`` a download, instants of the download's kind, the landed
    one inside its span; ``ck_download_seconds`` counts every download
    (issue to landed), tracer on or off; with the tracer off nothing is
    recorded."""
    from cekirdekler_tpu.metrics.registry import REGISTRY

    def count() -> int:
        return sum(v["count"] for k, v in REGISTRY.snapshot()[
            "histograms"].items() if k.startswith("ck_download_seconds"))

    n = 4096
    cr = NumberCruncher(_cpus(1), INC)
    x = ClArray(np.zeros(n, np.float32), name="marked_x", partial_read=True)
    kind = "download" if chunks == 1 else "download-chunk"
    try:
        cr.stream_chunks = chunks
        before = count()
        x.compute(cr, 79, "inc", n, 64)  # tracer off
        assert count() - before == chunks
        assert TRACER.total_recorded == 0 and not TRACER.active()
        with tracing() as tr:
            x.compute(cr, 79, "inc", n, 64)
        assert count() - before == 2 * chunks
    finally:
        cr.dispose()
    np.testing.assert_array_equal(np.asarray(x), 2.0)
    spans = [s for s in tr.snapshot() if s.kind == kind]
    marks = [s for s in spans if _is_mark(s.tag)]
    downloads = [s for s in spans if s not in marks]
    assert len(downloads) == chunks and all(s.t0 == s.t1 for s in marks)
    issued = [s for s in marks if s.tag == "part:issued"]
    landed = [s for s in marks if s.tag == "part:landed"]
    assert len(issued) == len(landed) == chunks
    assert all(s.lane == 0 for s in marks)
    for span, mark in zip(downloads, landed):
        assert span.t0 <= mark.t0 <= span.t1
    assert max(s.t0 for s in issued) <= min(s.t0 for s in landed)
    # no other kind got a mark of the read-back's
    assert not [s for s in tr.snapshot() if s.kind != kind
                and s.tag in ("part:issued", "part:landed")]


def test_the_read_backs_marks_off_cost_one_active_check():
    """``download_async`` asks ``active()`` before it builds a mark's
    metadata: the off path, under the budget of a span's."""
    tr = Tracer()
    n = 50_000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            if tr.active():
                raise AssertionError("off")
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 1e-6, f"active() off cost {best*1e9:.0f} ns >= 1 µs"


def test_per_call_compute_marks_its_parts_and_a_deferred_one_nothing(
        profiled):
    """The window's per-call compute: ``submit``, ``join``, ``note`` (no
    exchange: no ``stage``) as ``ck/engage`` instants on the caller's
    thread, and ONE ``phase-done`` a lane from the pool's threads; a
    deferred compute has no mark inside it."""
    spans, outer = _inside(profiled, "test/warm", "enqueue")
    caller = [e for e in spans if e.line == outer.line]
    per_call = [e for e in caller
                if not str(e.stats["tag"]).endswith("fused-defer")]
    deferred = [e for e in caller if e not in per_call]
    assert len(per_call) == 1 and deferred
    tags, marks = _marks_in(profiled, per_call[0], "engage")
    # (the lanes' launches mark ``part:call`` / ``part:handed`` on the kind
    # too, from the pool's threads: ISSUE 52, the tests further down)
    marks = [e for e in marks if e.line == outer.line]
    assert [str(e.stats["tag"]) for e in marks] == [
        "part:submit", "part:join", "part:note"]
    assert len(tags) == len(marks) + 2 * 2  # one pair a lane's launch
    assert all(e.stats["cid"] == 77 for e in marks)
    phases, events = _marks_in(profiled, per_call[0], "enqueue")
    for lane in (0, 1):
        assert [str(e.stats["tag"]) for e in events
                if e.stats["lane"] == lane] == [
            "phase-start", "phase-locked", "phase-done"]
    assert len(phases) == 6
    assert all(e.line != outer.line and "queued_us" not in e.stats
               for e in events)
    for kind in ("engage", "enqueue", "fence", "resync"):
        assert all(_marks_in(profiled, e, kind)[0] == [] for e in deferred)
    # every ck/enqueue event on the caller's thread is a compute: a mark
    # there would count as one (benchmark/host_phases.py)
    everything = [e for e in profiled.events if e.name == "ck/enqueue"
                  and e.line == outer.line
                  and outer.t0 <= e.t0 and e.t1 <= outer.t1]
    assert len(everything) == profiled.calls


@pytest.mark.parametrize("lanes", [1, 2])
def test_marks_leave_the_rings_per_kind_totals_alone(lanes):
    """``window_report`` over one window's spans with the marks and with
    them taken out: every kind's milliseconds and the covered time are the
    same (a mark has no length); a one-lane barrier has no ``feed``, and
    its ``retired`` is on the caller's own thread."""
    n = 2048
    cr = NumberCruncher(_cpus(lanes), INC)
    x = ClArray(np.zeros(n, np.float32), name="marks_x", partial_read=True)
    try:
        cr.enqueue_mode = True
        with tracing() as tr:
            t0 = time.perf_counter()
            for _ in range(3):
                x.compute(cr, 78, "inc", n, 64)
            cr.barrier()
            t1 = time.perf_counter()
        cr.enqueue_mode = False
    finally:
        cr.dispose()
    spans = tr.snapshot()
    marks = [s for s in spans if _is_mark(s.tag)]
    assert all(s.t0 == s.t1 for s in marks)
    (fence,) = [s for s in spans if s.kind == "fence" and s.tag == "barrier"]
    # (a compute whose transfer the tuner is measuring fences its own lane:
    # that ``retired`` lies inside the compute, not here)
    barrier = [s for s in marks if s.kind == "fence"
               and fence.t0 <= s.t0 <= fence.t1]
    want = ["part:wait"] + ["retired"] * lanes + ["part:feed"] * (
        lanes > 1) + ["part:close"]
    assert [s.tag for s in barrier] == want, barrier
    assert sorted(s.lane for s in barrier if s.tag == "retired") == list(
        range(lanes))
    # a new kernel's first two computes go per call, the third is deferred
    per_call = sum(s.kind == "enqueue" and s.t1 > s.t0
                   and not s.tag.endswith("fused-defer") for s in spans)
    assert per_call == 2
    assert [s.tag for s in marks if s.kind == "engage"
            and s.lane is None] == [
        "part:submit", "part:join", "part:note"] * per_call
    for tag in ("phase-start", "phase-locked", "phase-done"):
        assert sum(s.tag == tag for s in marks) == lanes * per_call
    # one pair a launch (the per-call computes' and the fused dispatch's)
    pairs = [s.tag for s in marks if s.kind == "engage"
             and s.lane is not None]
    launches = sum(s.kind == "launch" for s in spans)
    assert sorted(pairs) == ["part:call"] * launches + [
        "part:handed"] * launches and launches >= lanes * per_call
    with_marks = window_report(spans, t0, t1)
    without = window_report([s for s in spans if s not in marks], t0, t1)
    assert set(with_marks.per_kind) == set(without.per_kind)
    for kind, row in without.per_kind.items():
        assert with_marks.per_kind[kind]["ms"] == row["ms"], kind
    assert with_marks.covered_ms == without.covered_ms
    assert with_marks.n_spans == without.n_spans + len(marks)


def test_a_mark_off_costs_what_a_span_off_costs():
    """The off path of a mark is ``instant()``'s falsy path, under the
    same budget as ``test_disabled_tracer_overhead_under_budget``."""
    tr = Tracer()
    assert not tr.active()
    n = 50_000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            tr.instant("fence", tag="part:wait")
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 1e-6, f"disabled mark cost {best*1e9:.0f} ns >= 1 µs"
    assert tr.total_recorded == 0


_LANE_ORDER = ["phase-start", "phase-locked", "launch", "part:call",
               "part:handed", "part:issued", "part:landed", "phase-done"]


def _one_synchronous_compute(lanes, chunks, spy=None):
    """The ring of ONE warm non-windowed ``compute()`` on ``lanes`` lanes,
    ``stream_chunks`` pinned to ``chunks`` (1: the monolithic engine)."""
    n = 4096
    cr = NumberCruncher(_cpus(lanes), INC)
    x = ClArray(np.zeros(n, np.float32), name="edge_x", partial_read=True)
    try:
        cr.stream_chunks = chunks
        x.compute(cr, 81, "inc", n, 64)
        with tracing() as tr:
            if spy is not None:
                spy()
            x.compute(cr, 81, "inc", n, 64)
    finally:
        cr.dispose()
    np.testing.assert_array_equal(np.asarray(x), 2.0)
    return tr.snapshot()


@pytest.mark.parametrize("lanes, chunks", [(1, 1), (1, 4), (2, 1)],
                         ids=["monolithic", "streamed", "two-lanes"])
def test_a_synchronous_compute_tells_its_lanes_edge_in_order(lanes, chunks):
    """ISSUE 52: on each lane the ring of one synchronous compute holds
    ``phase-start`` <= ``phase-locked`` <= the first ``launch`` open <=
    ``part:call`` <= ``part:handed`` <= ``part:issued`` <= the last
    ``part:landed`` <= ``phase-done``; ONE ``part:call`` / ``part:handed``
    pair a ``launch`` span, inside it; the four new instants carry the
    lane and the compute's id, on kinds that exist."""
    spans = _one_synchronous_compute(lanes, chunks)
    (call,) = [s for s in spans if s.kind == "enqueue" and s.t1 > s.t0]
    submit, note = (next(s.t0 for s in spans if s.tag == tag)
                    for tag in ("part:submit", "part:note"))
    for lane in range(lanes):
        mine = [s for s in spans if s.lane == lane]
        at = {tag: [s.t0 for s in mine if s.tag == tag]
              for tag in _LANE_ORDER}
        launches = sorted((s for s in mine if s.kind == "launch"),
                          key=lambda s: s.t0)
        assert len(launches) == chunks
        assert [len(at[t]) for t in ("phase-start", "phase-locked",
                                     "phase-done")] == [1, 1, 1]
        assert len(at["part:call"]) == len(at["part:handed"]) == chunks
        assert len(at["part:issued"]) == len(at["part:landed"]) == chunks
        for span, called, handed in zip(launches, sorted(at["part:call"]),
                                        sorted(at["part:handed"])):
            assert span.t0 <= called <= handed <= span.t1
        chain = [submit, at["phase-start"][0], at["phase-locked"][0],
                 launches[0].t0, min(at["part:call"]),
                 min(at["part:handed"]), min(at["part:issued"]),
                 max(at["part:landed"]), at["phase-done"][0], note]
        assert chain == sorted(chain), chain
        assert call.t0 <= chain[0] and chain[-1] <= call.t1
        new = [s for s in mine if s.tag in (
            "phase-start", "phase-locked", "part:call", "part:handed")]
        assert all(s.cid == 81 and s.t0 == s.t1 for s in new)
        assert {s.kind for s in new} == {"enqueue", "engage"}
    # nothing of kind ``launch`` but the launches themselves: a reader that
    # counts a lane's ``ck/launch`` events counts what it counted
    assert all(s.t1 > s.t0 for s in spans if s.kind == "launch")


def test_phase_start_carries_the_hop_under_a_key_of_its_own(monkeypatch):
    """``hop_us`` on ``phase-start`` (the closure's wait between the
    caller's submit and the lane's first line) and ``queued_us`` on no
    instant: ``driver_queue_wait_ms_per_call`` sums that key over spans
    and must read what it read."""
    seen = []
    instant = TRACER.instant

    def spy():
        def spied(kind, cid=None, lane=None, tag=None, **meta):
            seen.append((kind, tag, lane, meta))
            return instant(kind, cid=cid, lane=lane, tag=tag, **meta)

        monkeypatch.setattr(TRACER, "instant", spied)

    _one_synchronous_compute(2, 1, spy)
    starts = [m for kind, tag, _lane, m in seen if tag == "phase-start"]
    assert len(starts) == 2
    assert all(set(m) == {"hop_us"} and m["hop_us"] >= 0.0 for m in starts)
    assert not [m for _k, _t, _l, m in seen if "queued_us" in m]
    assert sorted(lane for _k, tag, lane, _m in seen
                  if tag == "phase-locked") == [0, 1]
    # outside a bound closure there is no hop to tell
    assert TRACER.hop_meta() == {}


def test_the_lanes_marks_ride_the_profile_with_their_lane(profiled):
    """In a profiler session the four instants are annotations of kinds
    that exist: ``ck/enqueue`` ``phase-start`` (``hop_us``, no
    ``queued_us``) and ``phase-locked`` from the pool's threads, ONE
    ``ck/engage`` ``part:call`` / ``part:handed`` pair inside every
    ``ck/launch``, with its lane; and ``ck/launch`` events are launches."""
    launches = [e for e in profiled.events if e.name == "ck/launch"]
    pairs = [e for e in profiled.events if e.name == "ck/engage"
             and str(e.stats.get("tag")) in ("part:call", "part:handed")]
    assert len(pairs) == 2 * len(launches)
    for span in launches:
        inside = sorted((e for e in pairs if e.line == span.line
                         and span.t0 <= e.t0 and e.t1 <= span.t1),
                        key=lambda e: e.t0)
        assert [str(e.stats["tag"]) for e in inside] == [
            "part:call", "part:handed"]
        assert all(e.stats["lane"] == span.stats["lane"]
                   and e.stats["cid"] == 77 and "queued_us" not in e.stats
                   for e in inside)
    starts = [e for e in profiled.events if e.name == "ck/enqueue"
              and e.stats.get("tag") == "phase-start"]
    locked = [e for e in profiled.events if e.name == "ck/enqueue"
              and e.stats.get("tag") == "phase-locked"]
    assert starts and len(starts) == len(locked)
    assert all(e.stats["hop_us"] >= 0.0 and "queued_us" not in e.stats
               and e.stats["lane"] in (0, 1) for e in starts)
    assert not [e for e in launches if _is_mark(e.stats.get("tag"))]


def test_the_lanes_marks_off_cost_one_check_a_site():
    """The four new sites with the tracer off: ``_run_worker`` asks
    ``active()`` once for its two, ``Worker.launch`` tests its own span's
    token for its pair: under the budget of a span's, all four."""
    tr = Tracer()
    assert not tr.active()
    token = tr.t0("launch")
    n = 50_000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            on = tr.active()
            if on:
                raise AssertionError("off")
            if on:
                raise AssertionError("off")
            if token:
                raise AssertionError("off")
            if token:
                raise AssertionError("off")
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 1e-6, f"four sites off cost {best*1e9:.0f} ns >= 1 µs"


def test_first_launch_yields_compile_and_second_none(profiled):
    cold, _ = _inside(profiled, "test/cold", "compile")
    assert cold and all("inc" in str(e.stats["tag"]) for e in cold)
    assert any(str(e.stats["tag"]).startswith("fused:inc") for e in cold)
    # spans of the pool / driver threads name the lane they worked for
    assert all(e.stats.get("lane") in (0, 1) for e in cold)
    assert _inside(profiled, "test/warm", "compile")[0] == []


def test_one_annotation_per_launch_marks_emit_none(profiled):
    """MARKS opens no annotation of its own: every launch is ONE
    ``ck/launch`` in the dump, which carries the mark's seq and kernel."""
    assert not [e for e in profiled.events if e.name.startswith("ck|")]
    launches = [e for e in profiled.events if e.name == "ck/launch"]
    assert len(launches) == len(profiled.marks)
    by_seq = {m.seq: m for m in profiled.marks}
    assert {e.stats["seq"] for e in launches} == set(by_seq)
    assert all(by_seq[e.stats["seq"]].kernel == e.stats["kernel"] == "inc"
               for e in launches)


def test_mark_pairs_anchor_trace_time_onto_perf_counter(profiled):
    """The launch span seen host-side (the Mark) and in the dump (the
    ``ck/launch`` annotation) is one event on two clocks: every pair gives
    the same offset, to well under a launch's own length."""
    by_seq = {m.seq: m for m in profiled.marks}
    offs = [by_seq[e.stats["seq"]].t0 - e.t0 * 1e-9
            for e in profiled.events if e.name == "ck/launch"]
    assert len(offs) >= 4 and max(offs) - min(offs) < 5e-3


def test_a_synchronous_computes_launch_span_counts_its_packed_scalars(
        tmp_path):
    """ISSUE 39: a per-call dispatch hands its run-time scalars over as one
    vector, and the ``ck/launch`` / ``ck/compile`` spans say so: mandelbrot's
    four floats, two ints and the offset are ``scalars=packed:7;loose:0`` on
    the XLA half (the rig's lanes), cold and warm."""
    import jax
    from jax.profiler import ProfileData

    from cekirdekler_tpu.workloads import MANDELBROT_SRC

    wh = 64
    cr = NumberCruncher(_cpus(1), MANDELBROT_SRC)
    out = ClArray(wh * wh, np.float32, name="shown", read=False, write=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for x0 in (-2.0, -1.75):  # the view moves: nothing is kept by value
            out.compute(cr, 3901, "mandelbrot", wh * wh, 64,
                        values=(x0, -1.25, 2.5 / wh, 2.5 / wh, wh, 32))
    finally:
        jax.profiler.stop_trace()
        cr.dispose()
    path = [os.path.join(r, f) for r, _d, fs in os.walk(str(tmp_path))
            for f in fs if f.endswith(".xplane.pb")][0]
    spans = [(ev.name, dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name in ("ck/launch", "ck/compile")]
    launches = [st for name, st in spans if name == "ck/launch"]
    compiles = [st for name, st in spans if name == "ck/compile"]
    assert len(launches) == 2 and len(compiles) == 1
    for st in launches + compiles:
        assert st["lowering"] == "xla"
        assert st["scalars"] == "packed:7;loose:0"
    assert [str(st["tag"]) for st in launches] == ["mandelbrot x1"] * 2


def test_a_compactable_loops_spans_carry_the_compact_field(tmp_path):
    """ISSUE 41: Rodinia's ``BFS_1`` over a launch wider than a chunk builds
    its adjacency loop both ways, and the ``ck/launch`` / ``ck/compile``
    spans that ran the build say so (``compact=loops:1;width:W;gathered:3;
    scattered:0;ordered:1``: since ISSUE 53 its entering lanes go to their
    chunks by trip count); ``BFS_2`` has no loop and its spans no such field.  The
    ``access`` field stays as PR 40 left it."""
    import jax
    from jax.profiler import ProfileData

    from cekirdekler_tpu.kernel import codegen

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(os.path.dirname(here), "benchmark", "configs",
                           "rodinia_bfs.cl"), encoding="utf-8") as f:
        src = f.read()
    width = codegen._COMPACT_WIDTH
    n = 2 * width
    rng = np.random.default_rng(7)
    host = {"starting": 2 * np.arange(n, dtype=np.int32),
            "no_of_edges": np.full(n, 2, np.int32),
            "edges": rng.integers(0, n, 2 * n).astype(np.int32),
            "mask": (rng.random(n) < 0.05).astype(np.int8),
            "updating": np.zeros(n, np.int8),
            "visited": (rng.random(n) < 0.5).astype(np.int8),
            "cost": np.zeros(n, np.int32), "over": np.zeros(1, np.int8)}
    arr = {k: ClArray(v, name=k) for k, v in host.items()}
    arr["over"].write_all = True
    first, *rest = arr.values()
    group = first.next_param(*rest)
    cr = NumberCruncher(_cpus(1), src)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for kernels in ("BFS_1 BFS_2", "BFS_1 BFS_2", "BFS_2"):
            group.compute(cr, 4101, kernels, n, 256, values=(n,))
    finally:
        jax.profiler.stop_trace()
        cr.dispose()
    path = [os.path.join(r, f) for r, _d, fs in os.walk(str(tmp_path))
            for f in fs if f.endswith(".xplane.pb")][0]
    spans = [(ev.name, dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name in ("ck/launch", "ck/compile")]
    field = f"loops:1;width:{width};gathered:3;scattered:0;ordered:1"
    launches = [st for name, st in spans if name == "ck/launch"]
    compiles = [st for name, st in spans if name == "ck/compile"]
    with_loop = [st for st in launches if "BFS_1" in str(st["tag"])]
    without = [st for st in launches if "BFS_1" not in str(st["tag"])]
    assert with_loop and without
    assert {st.get("compact") for st in with_loop} == {field}
    assert all("compact" not in st for st in without)
    for st in with_loop:
        kinds = str(st["access"]).split(";")
        assert {"scatter:2", "gather:2"} <= set(kinds)
        assert st["scatter"] == "stores:2;width:4+1"
    assert any("uniform:1" in str(st["access"]).split(";") for st in without)
    built = {str(st["tag"]).split()[0]: st for st in compiles
             if f"chunk={n} " in str(st["tag"])}
    assert set(built) == {"BFS_1", "BFS_2"}
    assert built["BFS_1"]["compact"] == field
    assert all("compact" not in st for st in compiles
               if str(st["tag"]).startswith("BFS_2"))


def test_mosaic_launch_carries_the_kernels_name():
    """The device operation and the XLA module are named after the user's
    kernel: the lowering for a TPU names the Mosaic call ``inc`` and the
    module ``jit_inc``; the fused ladder's is ``jit_fused_inc``."""
    import jax
    import jax.numpy as jnp

    from cekirdekler_tpu.kernel.codegen import hlo_name
    from cekirdekler_tpu.kernel.registry import KernelProgram

    prog = KernelProgram(INC)
    fn, info = prog.launcher("inc", 4096, 64, 8192, platform="tpu")
    assert info.lowering == "pallas"
    buf = jax.ShapeDtypeStruct((8192,), jnp.float32)
    off = jax.ShapeDtypeStruct((), jnp.int32)
    text = fn.trace(off, (buf,), ()).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and 'kernel_name = "inc"' in text
    assert "module @jit_inc " in text
    fused = prog.fused_launcher(("inc",), 64, 8192, 64, 8192, (),
                                platform="cpu")
    text = fused.trace(off, off, off, (buf,)).lower().as_text()
    assert "module @jit_fused_inc " in text
    # the XLA-lowered path shows the kernel in its module name too
    fn_cpu, _ = prog.launcher("inc", 4096, 64, 8192, platform="cpu")
    assert "module @jit_inc " in fn_cpu.trace(
        off, (buf,), ()).lower().as_text()
    assert hlo_name("a-b c", "k2") == "a_b_c.k2"
