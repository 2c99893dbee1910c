"""Dedicated coverage for the seed-era ``utils/events.py`` and
``utils/timeline.py`` (neither had its own tests; the flight recorder
and debug plane build on their idioms, so their semantics are pinned
here first).

UserEvent runs against whichever tier loaded (native condition-variable
or the pure-Python fallback) — the CONTRACT is identical either way:
trigger fires, the pending counter fires at zero, waits time out.
Timeline analysis is pinned against a synthetic Xprof trace file so the
reduction (device tracks → busy/span) is deterministic."""

import gzip
import json
import os
import threading
import time
from contextlib import contextmanager

import pytest

from cekirdekler_tpu.utils import timeline as tl
from cekirdekler_tpu.utils.events import UserEvent
from cekirdekler_tpu.utils.timeline import (
    DeviceTimeline,
    _merged_busy,
    analyze_trace_dir,
)


# ---------------------------------------------------------------------------
# UserEvent (ClUserEvent parity semantics)
# ---------------------------------------------------------------------------

def test_user_event_trigger_and_fired():
    ev = UserEvent()
    try:
        assert ev.fired() is False
        assert ev.wait(timeout=0.05) is False  # untriggered wait times out
        ev.trigger()
        assert ev.fired() is True
        assert ev.wait(timeout=0.05) is True   # already fired: immediate
    finally:
        ev.close()


def test_user_event_counter_fires_at_zero():
    ev = UserEvent()
    try:
        ev.increment()
        ev.increment()
        assert ev.pending() == 2
        ev.decrement()
        assert ev.fired() is False  # one contributor still pending
        assert ev.pending() == 1
        ev.decrement()
        assert ev.fired() is True   # last decrement fires
    finally:
        ev.close()


def test_user_event_releases_a_blocked_waiter():
    ev = UserEvent()
    released = threading.Event()

    def waiter():
        if ev.wait(timeout=10.0):
            released.set()

    t = threading.Thread(target=waiter)
    t.start()
    try:
        time.sleep(0.05)
        assert not released.is_set()  # genuinely blocked
        ev.trigger()
        t.join(timeout=10.0)
        assert released.is_set()
    finally:
        t.join(timeout=1.0)
        ev.close()


def test_user_event_close_is_idempotent():
    ev = UserEvent()
    ev.close()
    ev.close()  # double close must be harmless (the __del__ path)


# ---------------------------------------------------------------------------
# timeline: interval union + trace-dir reduction
# ---------------------------------------------------------------------------

def test_merged_busy_unions_overlaps():
    assert _merged_busy([]) == 0.0
    assert _merged_busy([(0.0, 10.0)]) == 10.0
    # overlapping + disjoint + contained
    assert _merged_busy(
        [(0.0, 5.0), (3.0, 8.0), (20.0, 25.0), (21.0, 22.0)]
    ) == pytest.approx(13.0)


def test_device_timeline_busy_fraction():
    assert DeviceTimeline().compute_busy_fraction == 0.0  # no div-by-zero
    t = DeviceTimeline(compute_busy_ms=3.0, span_ms=4.0)
    assert t.compute_busy_fraction == pytest.approx(0.75)


def _write_trace(dirpath, events, name="host.trace.json.gz"):
    os.makedirs(dirpath, exist_ok=True)
    path = os.path.join(dirpath, name)
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)
    return path


def test_analyze_trace_dir_reduces_device_tracks(tmp_path):
    events = [
        # device process + its XLA Ops track
        {"ph": "M", "name": "process_name", "pid": 7,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "thread_name", "pid": 7, "tid": 2,
         "args": {"name": "XLA Ops"}},
        # a second device
        {"ph": "M", "name": "process_name", "pid": 8,
         "args": {"name": "/device:TPU:1"}},
        {"ph": "M", "name": "thread_name", "pid": 8, "tid": 2,
         "args": {"name": "XLA Ops"}},
        # a host process that must be IGNORED
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "python"}},
        # device ops (ts/dur in µs): overlapping on dev 0
        {"ph": "X", "pid": 7, "tid": 2, "ts": 0.0, "dur": 1000.0},
        {"ph": "X", "pid": 7, "tid": 2, "ts": 500.0, "dur": 1000.0},
        {"ph": "X", "pid": 8, "tid": 2, "ts": 2000.0, "dur": 500.0},
        # an event on the device pid but a non-op track: ignored
        {"ph": "X", "pid": 7, "tid": 9, "ts": 0.0, "dur": 9999.0},
        # a host event: ignored
        {"ph": "X", "pid": 1, "tid": 0, "ts": 0.0, "dur": 9999.0},
    ]
    _write_trace(str(tmp_path / "plugins"), events)
    result = analyze_trace_dir(str(tmp_path))
    assert result.n_devices == 2
    assert result.n_events == 3
    # dev0 union = 1.5 ms, dev1 = 0.5 ms
    assert result.compute_busy_ms == pytest.approx(2.0)
    assert result.span_ms == pytest.approx(2.5)  # 0 .. 2500 µs
    assert result.per_device_busy_ms["/device:TPU:0"] == pytest.approx(1.5)
    assert result.compute_busy_fraction == pytest.approx(0.8)
    assert result.trace_path and result.trace_path.endswith(".trace.json.gz")


def test_analyze_trace_dir_picks_newest_and_survives_empty(tmp_path):
    assert analyze_trace_dir(str(tmp_path)).n_events == 0  # empty: empty
    old = [
        {"ph": "M", "name": "process_name", "pid": 7,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "thread_name", "pid": 7, "tid": 2,
         "args": {"name": "XLA Ops"}},
        {"ph": "X", "pid": 7, "tid": 2, "ts": 0.0, "dur": 100.0},
    ]
    new = list(old) + [
        {"ph": "X", "pid": 7, "tid": 2, "ts": 200.0, "dur": 100.0},
    ]
    p_old = _write_trace(str(tmp_path), old, name="a.trace.json.gz")
    os.utime(p_old, (1, 1))  # force mtime ordering regardless of fs clock
    _write_trace(str(tmp_path), new, name="b.trace.json.gz")
    result = analyze_trace_dir(str(tmp_path))
    assert result.n_events == 2  # the NEWEST file won


def test_capture_runs_region_when_profiler_unavailable(monkeypatch):
    import jax

    def broken_trace(_dir):
        raise RuntimeError("profiler unavailable on this backend")

    monkeypatch.setattr(jax.profiler, "trace", broken_trace)
    ran = []
    with tl.capture("/tmp/ck_never_written") as result:
        ran.append(True)  # the region still runs, untraced
    assert ran and result().n_events == 0


def test_capture_propagates_region_exception(monkeypatch, tmp_path):
    import jax

    exited = []

    class FakeProf:
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            exited.append(exc[0])

    monkeypatch.setattr(jax.profiler, "trace", lambda d: FakeProf())
    with pytest.raises(ValueError, match="inside region"):
        with tl.capture(str(tmp_path)):
            raise ValueError("inside region")
    # the profiler was stopped best-effort even though the region raised
    assert len(exited) == 1


def test_real_profiler_capture_reduces(tmp_path):
    """The REAL ``jax.profiler.trace`` format, alongside the synthetic
    fixture: capture actual jitted work with a ``TraceAnnotation``,
    then assert the shared loader and both reducers handle the genuine
    dump.  Gated on a NAMED capability — a jax build whose profiler
    cannot emit a trace-event dump skips, it does not fail."""
    import jax
    import jax.numpy as jnp

    d = str(tmp_path / "real")
    try:
        with jax.profiler.trace(d):
            with jax.profiler.TraceAnnotation(
                    "ck/launch", kernel="mm", cid=1, lane=0, seq=1):
                x = jnp.ones((128, 128))
                for _ in range(2):
                    x = (x @ x).block_until_ready()
    except Exception as e:  # noqa: BLE001 - capability, not correctness
        pytest.skip(f"rig lacks capability:jax-profiler-trace ({e!r})")
    path, events = tl.load_trace_events(d)
    if path is None or not events:
        pytest.skip(
            "rig lacks capability:xprof-trace-json (profiler ran but "
            "wrote no trace-event dump)")
    # the real format reduces without error; on a deviceless CPU rig
    # that means ZERO device events (the named-absence contract), on an
    # accelerator rig a consistent busy/span pair
    result = analyze_trace_dir(d)
    assert result.n_events >= 0
    if result.n_events:
        assert 0.0 < result.compute_busy_ms <= result.span_ms
        assert result.n_devices >= 1
    else:
        assert result.compute_busy_ms == 0.0 and result.n_devices == 0
    # the annotation is discoverable by the device-attribution parser —
    # the correlation seam trace/device.py builds on
    from cekirdekler_tpu.trace.device import parse_trace_dump

    dump = parse_trace_dump(d)
    assert dump.n_events == len(events)
    assert 1 in dump.dump_marks, (
        "TraceAnnotation did not surface in the real dump — the mark "
        "correlation contract diverged from this jax's trace format")
    assert dump.dump_marks[1]["kernel"] == "mm"


def test_timeline_tracer_regions_and_report(monkeypatch, tmp_path):
    fake = DeviceTimeline(compute_busy_ms=1.0, span_ms=2.0, n_events=3)

    @contextmanager
    def fake_capture(_dir):
        yield lambda: fake

    monkeypatch.setattr(tl, "capture", fake_capture)
    tr = tl.Tracer(str(tmp_path))
    with tr.region("warmup"):
        pass
    with tr.region("steady"):
        pass
    assert set(tr.regions) == {"warmup", "steady"}
    assert tr.regions["steady"].compute_busy_fraction == pytest.approx(0.5)
    rep = tr.report()
    assert "warmup" in rep and "50.0% busy" in rep
    assert tl.Tracer(str(tmp_path)).report() == "(no regions captured)"
