"""``trace/device.py`` — the device-timeline attribution subsystem
(ISSUE 8 acceptance gates).

Pinned here, against the synthetic-Xprof fixture format the CPU
container can produce deterministically:

- the per-kernel device report RECONCILES: per-kernel device time sums
  to ≤ the window wall, and the coverage fraction is explicit (never a
  silently-partial report);
- a two-kernel skewed window attributes ≥ 90% of device time to the
  correct kernel (through each correlation tier);
- the merged Perfetto trace round-trips with host spans and device ops
  on ONE timeline;
- profiler-off and CPU-only paths degrade to a NAMED absence, never a
  crash, and the disabled mark plane is free at the launch site;
- the persistent kernel-profile store keys by (signature, shape,
  blocks), survives torn lines, and answers best()/history();
- ``/profilez`` serves the last capture and the store index.
"""

import gzip
import json
import os
import time
import urllib.request

import numpy as np
import pytest

from cekirdekler_tpu.trace import device as dv
from cekirdekler_tpu.trace.device import (
    DeviceMarks,
    DeviceWindowReport,
    Mark,
    ProfileStore,
    correlate,
    mark_fields,
    parse_trace_dump,
    roofline_row,
    split_unified_trace,
    unified_chrome_trace,
)
from cekirdekler_tpu.trace.spans import Span


# ---------------------------------------------------------------------------
# fixture builders: the synthetic-Xprof format
# ---------------------------------------------------------------------------

def _device_meta(pid=7, name="/device:TPU:0", tid=2, track="XLA Ops"):
    return [
        {"ph": "M", "name": "process_name", "pid": pid,
         "args": {"name": name}},
        {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
         "args": {"name": track}},
    ]


def _mark_event(seq, kernel, cid=None, lane=None, ts=0.0, dur=50.0, pid=1):
    # the launch's one annotation: the tracer's ``ck/launch`` span, its
    # metadata as the trace-viewer JSON renders it (strings)
    args = {"seq": str(seq), "kernel": kernel}
    if cid is not None:
        args["cid"] = str(cid)
    if lane is not None:
        args["lane"] = str(lane)
    return {"ph": "X", "pid": pid, "tid": 0, "ts": ts, "dur": dur,
            "name": "ck/launch", "args": args}


def _op(ts, dur, name="fusion.1", pid=7, tid=2, args=None):
    e = {"ph": "X", "pid": pid, "tid": tid, "ts": ts, "dur": dur,
         "name": name}
    if args:
        e["args"] = args
    return e


def _write_dump(dirpath, events, gz=True):
    os.makedirs(dirpath, exist_ok=True)
    path = os.path.join(
        dirpath, "host.trace.json.gz" if gz else "host.trace.json")
    if gz:
        with gzip.open(path, "wt") as f:
            json.dump({"traceEvents": events}, f)
    else:
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)
    return path


# ---------------------------------------------------------------------------
# mark names
# ---------------------------------------------------------------------------

def test_mark_name_round_trip():
    f = mark_fields(_mark_event(42, "nBody", cid=7, lane=3))
    assert f == {"kernel": "nBody", "cid": 7, "lane": 3, "seq": 42}
    # a launch span without cid/lane parses them back to None
    f2 = mark_fields(_mark_event(1, "k"))
    assert f2["cid"] is None and f2["lane"] is None and f2["seq"] == 1
    assert mark_fields({"name": "not a mark"}) is None
    # no seq (MARKS was off when the span closed): not a usable mark
    assert mark_fields({"name": "ck/launch",
                        "args": {"kernel": "x"}}) is None
    # what DeviceMarks hands the span is what mark_fields reads back
    m = DeviceMarks()
    m.enable()
    tok = m.begin(("nBody",), 7, 3)
    assert DeviceMarks.meta(tok) == {"seq": 1, "kernel": "nBody"}
    assert DeviceMarks.meta(None) == {}


# ---------------------------------------------------------------------------
# parse + correlate: reconciliation
# ---------------------------------------------------------------------------

def test_report_reconciles_against_window(tmp_path):
    """Per-kernel device time ≤ per-track union ≤ window wall; the
    coverage fraction is explicit."""
    t0 = time.perf_counter()
    events = _device_meta() + [
        _mark_event(1, "nBody", cid=5, lane=0, ts=0.0),
        # 3 ops, overlapping pair: union = 1.5 + 0.5 = 2.0 ms
        _op(100.0, 1000.0), _op(600.0, 1000.0), _op(2000.0, 500.0),
    ]
    _write_dump(str(tmp_path), events)
    dump = parse_trace_dump(str(tmp_path))
    assert len(dump.ops) == 3 and dump.n_events == len(events)
    marks = [Mark(1, "nBody", 5, 0, t0, t0 + 0.00005)]
    wall_s = 0.010
    rep = correlate(dump, marks, window=(t0, t0 + wall_s))
    assert rep.absent is None
    assert rep.device_busy_ms == pytest.approx(2.0)
    per_kernel_sum = sum(k.device_ms for k in rep.kernels)
    assert per_kernel_sum <= rep.wall_ms
    assert per_kernel_sum == pytest.approx(rep.attributed_ms)
    assert rep.coverage_frac == pytest.approx(1.0)
    assert rep.unattributed_ms == pytest.approx(0.0)
    nb = rep.kernel("nBody")
    assert nb.op_count == 3 and nb.cids == [5]
    # inter-op idle: span 0.1..2.5 ms = 2.4, busy 2.0 → 0.4 idle
    assert nb.idle_ms == pytest.approx(0.4)
    assert rep.per_lane_overlap[0] == pytest.approx(2.0 / 10.0)
    # the serialized form carries the same reconciliation keys
    d = rep.to_dict()
    assert d["coverage_frac"] == pytest.approx(1.0)
    assert d["kernels"][0]["kernel"] == "nBody"


def test_unmatched_ops_are_explicit_not_silent(tmp_path):
    """Ops matching no mark stay unattributed: coverage < 1 and the
    remainder is carried in unattributed_ms — never silently dropped."""
    events = _device_meta() + [
        _op(100.0, 1000.0, name="mystery.op"),
    ]
    _write_dump(str(tmp_path), events)
    rep = correlate(parse_trace_dump(str(tmp_path)), [])  # no marks at all
    assert rep.absent is None
    assert rep.coverage_frac == 0.0
    assert rep.unattributed_ms == pytest.approx(1.0)
    assert rep.kernels == []


def test_two_kernel_skewed_window_attributes_90pct(tmp_path):
    """The acceptance gate: a 10:1 skewed two-kernel window puts ≥ 90%
    of device time on the correct kernel — via the kernel-name tier
    here (op names mention the kernels, as real XLA op names do)."""
    t0 = 1000.0  # fake perf_counter epoch; anchor comes from mark pairs
    events = _device_meta() + [
        _mark_event(1, "heavy", cid=3, lane=0, ts=0.0),
        _mark_event(2, "light", cid=4, lane=0, ts=100.0),
        # heavy: 10 ms total; light: 1 ms — interleaved late (async skew:
        # light's ops land AFTER heavy's even though dispatch overlapped)
        _op(200.0, 6000.0, name="fusion.heavy.1"),
        _op(6300.0, 4000.0, name="fusion.heavy.2"),
        _op(10400.0, 1000.0, name="fusion.light.1"),
    ]
    _write_dump(str(tmp_path), events)
    marks = [Mark(1, "heavy", 3, 0, t0 + 0.0000, t0 + 0.00005),
             Mark(2, "light", 4, 0, t0 + 0.0001, t0 + 0.00015)]
    rep = correlate(parse_trace_dump(str(tmp_path)), marks,
                    window=(t0, t0 + 0.02))
    heavy, light = rep.kernel("heavy"), rep.kernel("light")
    assert heavy is not None and light is not None
    assert heavy.device_ms / (heavy.device_ms + light.device_ms) >= 0.90
    assert heavy.device_ms == pytest.approx(10.0)
    assert light.device_ms == pytest.approx(1.0)
    assert rep.matched_by == {"kernel-name": 3}
    assert rep.anchor == "marks"


def test_explicit_tier_beats_name_and_stream_order(tmp_path):
    """An op carrying ck-seq attaches to THAT mark even when its name
    mentions another kernel and a later mark precedes it in time."""
    events = _device_meta() + [
        _mark_event(1, "a", cid=1, lane=0, ts=0.0),
        _mark_event(2, "b", cid=2, lane=0, ts=100.0),
        _op(5000.0, 1000.0, name="fusion.b.99", args={"ck-seq": 1}),
    ]
    _write_dump(str(tmp_path), events)
    rep = correlate(parse_trace_dump(str(tmp_path)), [])
    assert rep.kernel("a").op_count == 1
    assert rep.kernel("b") is None
    assert rep.matched_by == {"explicit": 1}


def test_stream_order_tier_is_the_fallback(tmp_path):
    """Anonymous ops attach to the latest mark dispatched at or before
    their start — the documented stream-order bound.  An op BEFORE the
    first mark was dispatched by something unmarked: it must stay
    unattributed (else coverage_frac could never read below 1.0)."""
    events = _device_meta() + [
        _mark_event(1, "first", ts=1000.0),
        _mark_event(2, "second", ts=5000.0),
        _op(100.0, 500.0, name="warmup.spill"),  # BEFORE every mark
        _op(2000.0, 500.0, name="anon.1"),   # after mark 1, before mark 2
        _op(6000.0, 500.0, name="anon.2"),   # after mark 2
    ]
    _write_dump(str(tmp_path), events)
    rep = correlate(parse_trace_dump(str(tmp_path)), [])
    assert rep.kernel("first").op_count == 1
    assert rep.kernel("second").op_count == 1
    assert rep.matched_by == {"stream-order": 2}
    assert rep.unattributed_ms == pytest.approx(0.5)
    assert rep.coverage_frac == pytest.approx(1.0 / 1.5)


def test_kernel_name_tier_prefers_longest_match(tmp_path):
    """Substring-ambiguous names resolve to the most specific kernel:
    'fusion.add_fused.3' belongs to 'add_fused', never 'add'."""
    events = _device_meta() + [
        _mark_event(1, "add", ts=0.0),
        _mark_event(2, "add_fused", ts=100.0),
        _op(1000.0, 500.0, name="fusion.add_fused.3"),
        _op(2000.0, 300.0, name="fusion.add.1"),
    ]
    _write_dump(str(tmp_path), events)
    rep = correlate(parse_trace_dump(str(tmp_path)), [])
    assert rep.kernel("add_fused").op_count == 1
    assert rep.kernel("add").op_count == 1
    assert rep.kernel("add_fused").device_ms == pytest.approx(0.5)
    assert rep.kernel("add").device_ms == pytest.approx(0.3)


def test_window_clipping_counts_clipped_ops(tmp_path):
    t0 = 50.0
    events = _device_meta() + [
        _mark_event(1, "k", ts=0.0),
        _op(100.0, 1000.0, name="in.window"),
        _op(50_000.0, 1000.0, name="past.window"),
    ]
    _write_dump(str(tmp_path), events)
    marks = [Mark(1, "k", None, None, t0, t0 + 0.00005)]
    rep = correlate(parse_trace_dump(str(tmp_path)), marks,
                    window=(t0, t0 + 0.010))  # 10 ms window
    assert rep.n_ops == 1            # the out-of-window op was dropped
    assert rep.clipped_ops == 1
    assert rep.kernel("k").device_ms == pytest.approx(1.0)


def test_module_track_fallback_no_double_count(tmp_path):
    """A dump with BOTH "XLA Ops" and "XLA Modules" tracks must count
    only the op track; a dump with only a module track uses it."""
    both = (
        _device_meta(tid=2, track="XLA Ops")
        + [{"ph": "M", "name": "thread_name", "pid": 7, "tid": 3,
            "args": {"name": "XLA Modules"}}]
        + [_op(0.0, 1000.0, tid=2), _op(0.0, 1000.0, tid=3)]
    )
    _write_dump(str(tmp_path / "both"), both)
    rep = correlate(parse_trace_dump(str(tmp_path / "both")), [])
    assert rep.device_busy_ms == pytest.approx(1.0)  # not 2.0

    mod_only = (
        _device_meta(tid=3, track="XLA Modules") + [_op(0.0, 1000.0, tid=3)]
    )
    _write_dump(str(tmp_path / "mod"), mod_only)
    rep2 = correlate(parse_trace_dump(str(tmp_path / "mod")), [])
    assert rep2.device_busy_ms == pytest.approx(1.0)


def test_empty_dump_is_named_absence(tmp_path):
    rep = correlate(parse_trace_dump(str(tmp_path)), [])
    assert rep.absent is not None and "profiler" in rep.absent
    # events but no device tracks (the CPU-container shape)
    _write_dump(str(tmp_path), [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "python"}},
        _op(0.0, 100.0, pid=1, tid=0),
    ])
    rep2 = correlate(parse_trace_dump(str(tmp_path)), [])
    assert rep2.absent is not None and "device" in rep2.absent


# ---------------------------------------------------------------------------
# unified Perfetto export round trip
# ---------------------------------------------------------------------------

def test_unified_trace_round_trips_host_and_device(tmp_path):
    t0 = 2000.0
    events = _device_meta() + [
        _mark_event(1, "heavy", cid=3, lane=0, ts=0.0),
        _mark_event(2, "light", cid=4, lane=1, ts=100.0),
        _op(200.0, 5000.0, name="fusion.heavy.1"),
        _op(5400.0, 800.0, name="fusion.light.1", tid=2),
    ]
    _write_dump(str(tmp_path), events)
    marks = [Mark(1, "heavy", 3, 0, t0, t0 + 0.0001),
             Mark(2, "light", 4, 1, t0 + 0.0001, t0 + 0.0002)]
    rep = correlate(parse_trace_dump(str(tmp_path)), marks,
                    window=(t0, t0 + 0.02))
    spans = [
        Span("launch", t0 + 0.0000, t0 + 0.0001, cid=3, lane=0, tag="heavy"),
        Span("fence", t0 + 0.010, t0 + 0.012, lane=1),
    ]
    doc = unified_chrome_trace(spans, rep, ops=rep.ops, marks=marks)
    # serializes under the strict-JSON contract every exporter obeys
    json.dumps(doc, allow_nan=False)
    back_spans, back_ops = split_unified_trace(doc)
    assert [s.kind for s in back_spans] == ["launch", "fence"]
    assert {o.kernel for o in back_ops} == {"heavy", "light"}
    assert {o.lane for o in back_ops} == {0, 1}  # per-lane device tracks
    # ONE clock: every ts is relative to the common base — the heavy
    # device op starts AFTER the launch span that dispatched it
    launch = next(s for s in back_spans if s.kind == "launch")
    heavy_op = next(o for o in back_ops if o.kernel == "heavy")
    assert heavy_op.ts * 1e-6 >= launch.t0
    # device processes are named device:* and host pid survives
    names = [e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "process_name"]
    assert any(n.startswith("device:") for n in names)
    # the mark instants replay with the declared device-mark kind
    kinds = {e["args"].get("kind") for e in doc["traceEvents"]
             if e.get("ph") in ("i", "X") and "args" in e}
    assert "device-mark" in kinds and "device-op" in kinds


def test_unified_trace_without_device_side_is_plain_host_trace():
    spans = [Span("launch", 1.0, 1.01, lane=0)]
    doc = unified_chrome_trace(spans, None, ops=[], marks=[])
    back_spans, back_ops = split_unified_trace(doc)
    assert len(back_spans) == 1 and back_ops == []


# ---------------------------------------------------------------------------
# marks: disabled is free; enabled records
# ---------------------------------------------------------------------------

def test_disabled_marks_overhead_under_budget():
    """The launch-site guard (`if MARKS.enabled:`) must keep the
    disabled path at attribute-read cost — same pin discipline as the
    tracer's 1 µs budget."""
    m = DeviceMarks()
    assert not m.enabled
    n = 50_000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            tok = m.begin(("k",), 1, 0) if m.enabled else None
            if tok is not None:
                m.end(tok)
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 1e-6, f"disabled mark cost {best*1e9:.0f} ns >= 1 µs"
    assert m.total_recorded == 0


def test_enabled_marks_record_host_side_without_jax_annotation():
    m = DeviceMarks()
    m.enable()  # MARKS opens no annotation of its own: nothing to lack
    tok = m.begin(["a", "b"], cid=9, lane=2)
    assert tok is not None
    m.end(tok)
    m.disable()
    (mark,) = m.snapshot()
    assert mark.kernel == "a+b" and mark.cid == 9 and mark.lane == 2
    assert mark.t1 >= mark.t0 > 0.0
    assert m.begin(("k",), None, None) is None  # disabled again
    m.end(None)  # no-op by contract


def test_worker_launch_records_marks(cpu_devices):
    """The integration seam: a real framework compute() under MARKS
    produces host-side marks tagged with kernel/cid/lane."""
    import cekirdekler_tpu as ct
    from cekirdekler_tpu.arrays.clarray import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.trace.device import MARKS
    from cekirdekler_tpu.workloads import mandelbrot_pallas_kernel

    devs = ct.all_devices().cpus().subset(1)
    cr = NumberCruncher(devs, mandelbrot_pallas_kernel(interpret=True))
    out = ClArray(1024, np.float32, name="dm", read=False, write=True)
    vals = (-2.0, -1.25, 2.5 / 32, 2.5 / 32, 32, 8)
    try:
        MARKS.enable(clear=True)
        out.compute(cr, 4242, "mandelbrot", 1024, 256, values=vals)
        cr.barrier()
    finally:
        MARKS.disable()
        cr.dispose()
    marks = [m for m in MARKS.snapshot() if m.cid == 4242]
    assert marks, "launch recorded no device mark"
    assert marks[0].kernel == "mandelbrot" and marks[0].lane == 0


# ---------------------------------------------------------------------------
# capture degradation
# ---------------------------------------------------------------------------

def test_capture_profiler_off_degrades_to_named_absence(monkeypatch):
    from cekirdekler_tpu.obs.flight import FLIGHT
    from cekirdekler_tpu.utils import timeline

    monkeypatch.setattr(
        timeline, "start_profiler",
        lambda d: (None, "RuntimeError: no profiler on this backend"))
    ran = []
    with dv.capture_device("/tmp/ck_never_written_dev") as cap:
        ran.append(True)
    assert ran
    assert cap.report.absent is not None
    assert "profiler unavailable" in cap.report.absent
    assert cap.report.wall_ms > 0  # the window wall is still measured
    kinds = [e.kind for e in FLIGHT.snapshot()]
    assert "profiler-start" in kinds and "profiler-stop" in kinds
    # the named absence is what /profilez will serve
    assert dv.last_report() is cap.report


def test_capture_region_exception_propagates_and_names_absence(
        monkeypatch, tmp_path):
    from cekirdekler_tpu.utils import timeline

    monkeypatch.setattr(timeline, "start_profiler",
                        lambda d: (None, "unavailable"))
    with pytest.raises(ValueError, match="inside"):
        with dv.capture_device(str(tmp_path)):
            raise ValueError("inside")
    assert dv.last_report().absent is not None
    assert "ValueError" in dv.last_report().absent


def test_capture_parses_prewritten_dump(monkeypatch, tmp_path):
    """A capture whose profiler 'worked' (fake) and whose dir holds a
    synthetic dump produces a full report with marks correlated."""
    from cekirdekler_tpu.utils import timeline

    class FakeProf:
        pass

    monkeypatch.setattr(timeline, "start_profiler",
                        lambda d: (FakeProf(), None))
    monkeypatch.setattr(timeline, "stop_profiler", lambda h: None)
    with dv.capture_device(str(tmp_path)) as cap:
        # record one mark through the REAL plane while the window is open
        tok = dv.MARKS.begin("synthk", 11, 0)
        dv.MARKS.end(tok)
        seq = dv.MARKS.snapshot()[-1].seq
        _write_dump(str(tmp_path), _device_meta() + [
            _op(100.0, 2000.0, name="x", args={"ck-seq": seq}),
        ])
    rep = cap.report
    assert rep.absent is None
    prof = rep.kernel("synthk")
    # the synthetic 2 ms op is LONGER than the real (fast) window — the
    # reconciliation clips it to the wall instead of overcounting
    assert 0.0 < prof.device_ms <= rep.wall_ms
    assert prof.cids == [11]
    assert rep.anchor == "capture-start"  # mark absent from dump: fallback


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

def test_roofline_row_bounds_and_mfu():
    # memory-bound: intensity below the ridge; roof slanted by bandwidth
    r = roofline_row(flops=1e12, bytes_moved=1e11, device_ms=1000.0,
                     peak_tflops=200.0, peak_gbps=800.0)
    assert r["bound"] == "memory"
    assert r["intensity_flop_per_byte"] == pytest.approx(10.0)
    assert r["ridge_flop_per_byte"] == pytest.approx(250.0)
    assert r["attained_tflops"] == pytest.approx(1.0)
    assert r["roof_tflops"] == pytest.approx(8.0)  # 10 flop/B × 800 GB/s
    assert r["mfu"] == pytest.approx(1.0 / 200.0)
    assert r["frac_of_roof"] == pytest.approx(1.0 / 8.0)
    # compute-bound: intensity past the ridge caps at the flat roof
    r2 = roofline_row(flops=1e15, bytes_moved=1e9, device_ms=10_000.0,
                      peak_tflops=200.0, peak_gbps=800.0)
    assert r2["bound"] == "compute" and r2["roof_tflops"] == 200.0


# ---------------------------------------------------------------------------
# the persistent store
# ---------------------------------------------------------------------------

def test_store_disabled_without_root(monkeypatch):
    monkeypatch.delenv(dv.PROFILE_STORE_ENV, raising=False)
    st = ProfileStore()
    assert not st.enabled
    assert st.put("k", (8,), ("256",), {"device_ms": 1.0}) is None
    assert st.get("k", (8,), ("256",)) is None
    assert st.keys() == []


def test_store_put_get_history_best(tmp_path):
    st = ProfileStore(str(tmp_path))
    key = ("flash_attention.bf16_default", (2, 8192, 8, 64), (512, 512))
    p1 = st.put(*key, {"device_ms": 12.5, "mfu": 0.18})
    p2 = st.put(*key, {"device_ms": 9.75, "mfu": 0.24})
    p3 = st.put(*key, {"device_ms": 11.0, "mfu": 0.21})
    assert p1 == p2 == p3 and os.path.exists(p1)
    hist = st.history(*key)
    assert [r["device_ms"] for r in hist] == [12.5, 9.75, 11.0]
    assert all(r["schema"] == dv.STORE_SCHEMA for r in hist)
    assert st.get(*key)["device_ms"] == 11.0          # newest
    assert st.best(*key)["device_ms"] == 9.75         # measured floor
    # a DIFFERENT blocks geometry is a different key file
    st.put("flash_attention.bf16_default", (2, 8192, 8, 64), (1024, 512),
           {"device_ms": 1.0})
    assert len(st.keys()) == 2
    # rows carry the key fields the BlockTuner will filter on
    assert hist[0]["blocks"] == [512, 512]
    assert hist[0]["shape"] == [2, 8192, 8, 64]


def test_store_skips_torn_tail_line(tmp_path):
    st = ProfileStore(str(tmp_path))
    st.put("k", (1,), ("b",), {"device_ms": 3.0})
    path = st.path_for("k", (1,), ("b",))
    with open(path, "a") as f:
        f.write('{"schema": "ck-kernel-profile-v1", "device_ms": 1.0')
    assert [r["device_ms"] for r in st.history("k", (1,), ("b",))] == [3.0]
    assert st.best("k", (1,), ("b",))["device_ms"] == 3.0


# ---------------------------------------------------------------------------
# /profilez
# ---------------------------------------------------------------------------

def test_profilez_endpoint_serves_last_report_and_store(tmp_path):
    from cekirdekler_tpu.obs.debugserver import serve_debug

    dv._set_last_report(DeviceWindowReport(
        wall_ms=5.0, absent="no device op events in the dump (test)"))
    st = ProfileStore(str(tmp_path))
    st.put("k", (1,), ("b",), {"device_ms": 3.0})
    payload = dv.profilez_payload(store=st)
    assert payload["last_capture"]["absent"].startswith("no device op")
    assert payload["store"]["enabled"] and len(payload["store"]["keys"]) == 1

    srv = serve_debug(None)
    try:
        body = json.load(
            urllib.request.urlopen(srv.url + "/profilez", timeout=10))
        assert set(body) == {"last_capture", "marks", "store"}
        assert body["last_capture"]["wall_ms"] == 5.0
        # the index page advertises the endpoint
        idx = json.load(urllib.request.urlopen(srv.url + "/", timeout=10))
        assert "/profilez" in idx["endpoints"]
    finally:
        srv.close()


def test_plan_signature_blocks_component():
    from cekirdekler_tpu.core.stream import chunk_plan, plan_signature
    from cekirdekler_tpu.core.worker import _ladder

    assert plan_signature(chunk_plan(12 * 256, 256, 3)) == "1024+1024+1024"
    assert plan_signature(_ladder(12 * 256, 256)) == "2048+1024"
    assert plan_signature([]) == "0"
