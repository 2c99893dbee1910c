"""``mandelbrot_percall_1chip`` and ``mandelbrot_frame_1chip`` (configuration
``mandelbrot_display``) held to what the other cells are held to, at a 64 x 64
frame on the CPU container (``JAX_PLATFORMS=cpu python3 -m pytest
benchmark/checks/test_display_cells.py -q``), and the five ``readback_*``
readers held to a trace made by hand.  Nothing here yields a device number.

- the sound program reads ``correct`` true through both loops, exactly
  (limits 0 / 0 / 0), with exactly each cell's end-to-end metrics;
- the per-call cell's cycle leaves the LAST call's view in the caller's
  array, and a frame one call stale is caught;
- the control (the reference in bfloat16 in the program's place), a window of
  idle calls and a call whose read-back is skipped each read ``correct``
  false;
- the configuration, both cells and every new entry are in the manifest,
  found BY NAME (a later PR appends behind them).

The trace by hand: lane 0, window 10-12 s.  Call ``win`` 7 is one download:
issued 10.100, span 10.101-10.120, landed 10.115, 1000 bytes: whole 20 ms =
landing 15 + copy 5.  Call 8 is two chunks issued on the stream driver's
thread at 11.000 and 11.004 and finished on the phase thread: span
11.010-11.016 landed 11.012 (600 bytes), span 11.016-11.020 landed 11.017
(400 bytes): whole 20 ms = landing 12 + 1, copy 4 + 3.  Call 7 alone was
fenced before its read-back (the tuner's measuring run).  Call 6 lies before
the window, call 9 runs past its end, lane 1's download is another chip's.
"""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]

import cells  # noqa: E402
import host_phases  # noqa: E402
import run  # noqa: E402
import xplane  # noqa: E402

CONFIG = "mandelbrot_display"
PER_CALL, WINDOWED = "mandelbrot_percall_1chip", "mandelbrot_frame_1chip"
READBACK = ["readback_ms_per_call", "readback_landing_ms_per_call",
            "readback_copy_ms_per_call", "readback_rate",
            "readback_bytes_per_call"]
PER_LAYER = {
    PER_CALL: ["kernel_ms_per_iter.percall", "device_idle_share.percall",
               "window_compiles.percall", "resync_idle_ms_per_call.percall",
               "dispatch_idle_ms_per_call.percall",
               "fence_idle_ms_per_call.percall",
               "sched_idle_ms_per_call.percall",
               "unnamed_idle_share.percall", "device_copy_ms_per_call",
               "stream_chunks"] + READBACK,
    WINDOWED: ["kernel_ms_per_iter.frame1", "device_idle_share.frame1",
               "window_compiles.frame1", "unfused_computes_per_call.frame1",
               "dispatch_idle_ms_per_call.frame1",
               "fence_idle_ms_per_call.frame1", "unnamed_idle_share.frame1",
               "barrier_tail_ms_per_call.frame1",
               "window_head_ms_per_call.frame1",
               "trace_clock_violation_us.frame1"],
}
# the per-call cell reports the median under a variant with a bound of its
# own since PR 49 (its processes lie 0.6-0.8 % apart: PERF.md section 2)
MOVES = {PER_CALL: "call_p50_ms.percall", WINDOWED: "call_p50_ms"}
# 32 iterations: XLA's CPU backend contracts multiply-adds, which moves the
# chaotic orbits of a few boundary pixels at 256 (test_harness.py)
SMALL_CFG = {"width": 64, "height": 64, "sample_blocks": 8, "local_range": 64,
             "max_iter": 32}
SMALL_TRAFFIC = {PER_CALL: {"n": 4096},
                 WINDOWED: {"n": 4096, "iterations_per_call": 3}}


def small_cell(name: str) -> cells.Cell:
    cell = cells.load_cell(name)
    return cell._replace(cfg={**cell.cfg, **SMALL_CFG},
                         params={**cell.params, **SMALL_TRAFFIC[name]})


@pytest.fixture(scope="module")
def devices():
    from cekirdekler_tpu import hardware

    return hardware.chip_devices()  # the host CPU under JAX_PLATFORMS=cpu


def run_small(name, devices, seed=2**31 + 5, seconds=0.2):
    compared = []
    result = run.run_cell(small_cell(name), seed=seed, seconds=seconds,
                          trace=False, devices=devices,
                          compared_out=compared)
    return result, compared


# -- the program through both loops, against the reference ------------------

@pytest.mark.parametrize("name", [PER_CALL, WINDOWED])
def test_sound_program_is_exact_with_exactly_the_cells_metrics(name, devices):
    result, compared = run_small(name, devices)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {
        m["name"] for m in cells.load_cell(name).end_to_end}
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]  # the numbers compared last
    assert [(c.name, c.value, c.limit) for c in compared] == [
        ("pixels_differing", 0.0, 0), ("pixels_unwritten", 0.0, 0),
        ("calls_not_tiling", 0.0, 0)]


def test_the_cycle_pans_by_quarter_pixels_and_apart_is_no_view_of_it():
    cell = cells.load_cell(PER_CALL)
    _data, values = cell.ref.inputs(cell.cfg, cell.params,
                                    np.random.default_rng(0))
    plan = cell.ref.call_values(cell.cfg, cell.params, values)
    x0, y0, dx, dy, w, max_iter = values
    assert len(plan["cycle"]) == 4 and plan["cycle"][0] == tuple(values)
    for k, view in enumerate(plan["cycle"]):
        assert view[0] == float(np.float32(x0 + k / 4.0 * dx))
        assert view[1] == float(np.float32(y0 + k / 4.0 * dy))
        assert view[2:] == (dx, dy, w, max_iter)
    assert plan["apart"][5] == max_iter - 1 and plan["apart"] not in plan[
        "cycle"]
    # the windowed cell renders the configuration's view alone: a fused
    # window bakes the scalars
    windowed = cells.load_cell(WINDOWED)
    assert cell.ref.call_values(windowed.cfg, windowed.params, values)[
        "cycle"] == [tuple(values)]


def test_a_frame_one_call_stale_differs_along_the_sets_boundary():
    """At the cell's OWN frame, on the host alone: the reference's frames of
    two successive views differ in thousands of the sampled pixels."""
    cell = cells.load_cell(PER_CALL)
    _data, values = cell.ref.inputs(cell.cfg, cell.params,
                                    np.random.default_rng(0))
    cycle = cell.ref.call_values(cell.cfg, cell.params, values)["cycle"]
    px = cell.ref.sample(cell.cfg, cell.params, seed=1)
    frames = [cell.ref.orbit(v, px) for v in cycle]
    stale = [int((frames[k] != frames[k - 1]).sum()) for k in range(4)]
    assert px.size == 98304 and min(stale) > 3000, stale


def test_the_window_leaves_its_last_calls_view_and_a_stale_frame_is_caught(
        devices, monkeypatch):
    """The per-call loop goes through the cycle; what the caller's array
    holds is compared with the LAST call's view.  A program whose last call
    left the frame of the call before is the same array under the next
    view's arguments."""
    seen = []
    real_window = run.window

    def window(ctx, seconds, compiles):
        real_window(ctx, seconds, compiles)
        seen.append((len(ctx.walls), ctx.values, list(ctx.cycle)))

    monkeypatch.setattr(run, "window", window)
    result, compared = run_small(PER_CALL, devices)
    assert result["correct"] is True
    calls, last, cycle = seen[0]
    assert calls >= 4 and last == cycle[(calls - 1) % 4]

    def stale_window(ctx, seconds, compiles):
        real_window(ctx, seconds, compiles)
        # one call more is claimed than ran: the frame is one call old
        ctx.values = ctx.cycle[len(ctx.walls) % len(ctx.cycle)]

    monkeypatch.setattr(run, "window", stale_window)
    result, compared = run_small(PER_CALL, devices)
    assert result["correct"] is False
    assert compared[0].name == "pixels_differing" and compared[0].value > 0
    assert [c.ok for c in compared[1:]] == [True, True]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bfloat16_control_fails(seed):
    cell = small_cell(PER_CALL)
    data, values = cell.ref.inputs(cell.cfg, cell.params,
                                   np.random.default_rng(seed))
    plan = cell.ref.call_values(cell.cfg, cell.params, values)
    observed = {"iterations": 41, "outputs": None, "ranges_log": [],
                "values": plan["cycle"][-1],
                "fresh": {"iterations": 2, "outputs": None,
                          "values": plan["apart"]}}
    compared = cell.ref.compare(cell.cfg, cell.params, data, values,
                                observed, seed, precision="bfloat16")
    assert compared[0].name == "pixels_differing" and not compared[0].ok


@pytest.mark.parametrize("name", [PER_CALL, WINDOWED])
def test_window_of_idle_calls_is_not_correct(name, devices, monkeypatch):
    """Warm-up's last call left the frame set apart; a window whose calls do
    nothing leaves it there."""
    real_window = run.window

    def idle_window(ctx, seconds, compiles):
        call, ctx.call = ctx.call, lambda: None
        try:
            real_window(ctx, seconds, compiles)
        finally:
            ctx.call = call

    monkeypatch.setattr(run, "window", idle_window)
    result, compared = run_small(name, devices, seed=11, seconds=0.05)
    assert result["correct"] is False and result["attempted"] >= 1
    # the fresh call was sound, every pixel written: the window's frame is
    # the wrong one
    assert compared[0].value > 0 and compared[1].ok


def test_a_call_that_skips_its_read_back_is_not_correct(devices, monkeypatch):
    """The kernel runs and nothing comes back: inside the window the
    caller's array keeps the frame set apart; in the fresh call it keeps
    the poison."""
    from cekirdekler_tpu.core.worker import Worker

    real_window, real_finish = run.window, Worker.finish_download

    def window_without_read_back(ctx, seconds, compiles):
        Worker.finish_download = staticmethod(lambda handle: None)
        try:
            real_window(ctx, seconds, compiles)
        finally:
            Worker.finish_download = staticmethod(real_finish)

    monkeypatch.setattr(run, "window", window_without_read_back)
    result, compared = run_small(PER_CALL, devices, seed=13, seconds=0.05)
    assert result["correct"] is False
    assert compared[0].value > 0 and compared[1].value == 0

    def fresh_call_without_read_back(ctx, seconds, compiles):
        real_window(ctx, seconds, compiles)
        Worker.finish_download = staticmethod(lambda handle: None)

    monkeypatch.setattr(run, "window", fresh_call_without_read_back)
    try:
        result, compared = run_small(PER_CALL, devices, seed=13,
                                     seconds=0.05)
    finally:
        Worker.finish_download = staticmethod(real_finish)
    assert result["correct"] is False
    assert compared[1].name == "pixels_unwritten"
    assert compared[1].value == 4096.0


# -- the readers against a trace made by hand --------------------------------

DOWNLOAD, CHUNK = "ck/download", "ck/download-chunk"


def event(kind, start, end, line, lane=0, **stats) -> host_phases.HostSpan:
    return host_phases.HostSpan(kind, start, end, line,
                                {"lane": lane, **stats})


def mark(kind, at, line, tag, win, nbytes, lane=0) -> host_phases.HostSpan:
    return event(kind, at, at + 2e-6, line, lane, tag=tag, win=win,
                 bytes=nbytes, name="out", off=0)


def by_hand(marks: bool = True) -> list:
    phase = [  # the lane's phase thread
        mark(DOWNLOAD, 9.100, 1, "part:issued", 6, 1000),   # before the window
        event(DOWNLOAD, 9.101, 9.120, 1, tag="out", win=6, bytes=1000),
        mark(DOWNLOAD, 9.115, 1, "part:landed", 6, 1000),
        # the tuner's measuring run fenced the lane before this read-back
        event("ck/fence", 10.099, 10.099002, 1, tag="retired", win=7),
        mark(DOWNLOAD, 10.100, 1, "part:issued", 7, 1000),
        event(DOWNLOAD, 10.101, 10.120, 1, tag="out", win=7, bytes=1000),
        mark(DOWNLOAD, 10.115, 1, "part:landed", 7, 1000),
        event(CHUNK, 11.010, 11.016, 1, tag="out", win=8, bytes=600),
        mark(CHUNK, 11.012, 1, "part:landed", 8, 600),
        event(CHUNK, 11.016, 11.020, 1, tag="out", win=8, bytes=400),
        mark(CHUNK, 11.017, 1, "part:landed", 8, 400),
        mark(DOWNLOAD, 11.990, 1, "part:issued", 9, 1000),  # past the end
        event(DOWNLOAD, 11.991, 12.010, 1, tag="out", win=9, bytes=1000),
        mark(DOWNLOAD, 12.005, 1, "part:landed", 9, 1000),
        event("ck/launch", 10.050, 10.051, 1, tag="mandelbrot x1", win=7),
    ]
    driver = [  # the lane's stream driver thread
        mark(CHUNK, 11.000, 2, "part:issued", 8, 600),
        mark(CHUNK, 11.004, 2, "part:issued", 8, 400),
    ]
    other = [  # another chip's lane
        mark(DOWNLOAD, 10.500, 3, "part:issued", 7, 5000, lane=1),
        event(DOWNLOAD, 10.501, 10.600, 3, lane=1, tag="out", win=7,
              bytes=5000),
        mark(DOWNLOAD, 10.590, 3, "part:landed", 7, 5000, lane=1),
    ]
    lines = [[], phase, driver, other]
    if not marks:  # a parent commit: the spans alone
        lines = [[s for s in spans
                  if not str(s.stats.get("tag", "")).startswith("part:")]
                 for spans in lines]
    return lines


def ctx_by_hand(monkeypatch, lines) -> SimpleNamespace:
    monkeypatch.setattr(host_phases, "host_lines", lambda profile: lines)
    monkeypatch.setattr(xplane, "find_xplane", lambda _dir: os.path.join(
        HERE, "fixtures", "synthetic.xspace.txt"))
    reduced = xplane.Reduced(
        t0=10.0, t1=12.0, busy_s={0: 1.0}, op_seconds={0: {}},
        op_counts={0: {}}, idle_by_span={0: {}}, calls=3)
    workers = [SimpleNamespace(device=SimpleNamespace(id=0), index=0)]
    return SimpleNamespace(
        reduced=reduced,
        cr=SimpleNamespace(cores=SimpleNamespace(workers=workers)))


def test_read_back_is_cut_into_landing_and_copy_that_add_up():
    reader = cells.load_reader("readback_ms_per_call")
    r = reader.reduce(by_hand(), 10.0, 12.0, lane=0)
    assert (r.calls, r.downloads, r.bytes) == (2, 3, 2000.0)
    assert r.whole_s == pytest.approx(0.040)
    assert r.landing_s == pytest.approx(0.015 + 0.012 + 0.001)
    assert r.copy_s == pytest.approx(0.005 + 0.004 + 0.003)
    assert r.landing_s + r.copy_s == pytest.approx(r.whole_s)
    # call 7 was fenced before its read-back: its landing is the link alone
    assert (r.fenced_calls, r.fenced_bytes) == (1, 1000.0)
    assert r.fenced_landing_s == pytest.approx(0.015)
    # the other chip's lane reads its own download
    other = reader.reduce(by_hand(), 10.0, 12.0, lane=1)
    assert (other.calls, other.bytes) == (1, 5000.0)
    assert other.whole_s == pytest.approx(0.100)
    assert reader.reduce(by_hand(), 10.0, 12.0, lane=2) is None


def test_every_reader_reads_the_trace_by_hand(monkeypatch):
    ctx = ctx_by_hand(monkeypatch, by_hand())
    got = {m: cells.load_reader(m).read(ctx) for m in READBACK}
    assert got == {
        "readback_ms_per_call": pytest.approx(20.0),
        "readback_landing_ms_per_call": pytest.approx(14.0),
        "readback_copy_ms_per_call": pytest.approx(6.0),
        "readback_rate": pytest.approx(2000.0 / 0.040 / 1e9),
        "readback_bytes_per_call": pytest.approx(1000.0)}


def test_a_program_without_the_marks_leaves_every_reader_silent(monkeypatch):
    ctx = ctx_by_hand(monkeypatch, by_hand(marks=False))
    assert [cells.load_reader(m).read(ctx) for m in READBACK] == [None] * 5
    # and so does a window without downloads (the windowed cell)
    ctx = ctx_by_hand(monkeypatch, [[], [], [], []])
    assert [cells.load_reader(m).read(ctx) for m in READBACK] == [None] * 5


def test_a_download_without_its_landed_mark_drops_its_call():
    lines = by_hand()
    lines[1] = [s for s in lines[1]
                if not (s.stats.get("tag") == "part:landed"
                        and s.stats.get("win") == 8 and s.start > 11.015)]
    r = cells.load_reader("readback_ms_per_call").reduce(
        lines, 10.0, 12.0, lane=0)
    assert (r.calls, r.downloads, r.bytes) == (1, 1, 1000.0)


def test_variants_are_read_by_their_quantities_readers():
    for names in PER_LAYER.values():
        for name in names:
            assert cells.load_reader(name) is not None, name
    ctx = SimpleNamespace(window_compiles=0)
    assert cells.load_reader("window_compiles.percall").read(ctx) == 0.0
    assert cells.load_reader("window_compiles.frame1").read(ctx) == 0.0


# -- the manifest, by name ----------------------------------------------------

def test_the_configuration_cells_and_entries_are_in_the_manifest_by_name():
    man = cells.manifest()
    conf = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == [] and len(conf["source"]) <= 200
    assert "mandelbrot_bench_v4.rar" in conf["source"]
    assert "Kamera.cs:190-268" in conf["source"]
    others = [c["source"] for c in man["configs"] if c["name"] != CONFIG]
    assert conf["source"] not in others and len(conf["why"]) <= 200
    assert [c["file"] for c in man["configs"]].count(conf["file"]) == 1
    listed = {m["name"]: m for m in man["per_layer"]}
    ends = {m["name"]: m for m in man["end_to_end"]}
    for name in (PER_CALL, WINDOWED):
        row = next(w for w in man["workloads"] if w["name"] == name)
        assert row["chips"] == 1 and row["config"] == CONFIG
        assert row["traffic"] == name and len(row["why"]) <= 200
        cell = cells.load_cell(name)
        assert cell.cfg["source"] == conf["source"]
        assert cell.cfg["lanes"] == 1 and cell.cfg["reduced"] == []
        assert cell.cfg["kernel_file"] == "mandelbrot_frame.cl"
        assert cell.cfg["limits"] == {"pixels_differing": 0,
                                      "pixels_unwritten": 0,
                                      "calls_not_tiling": 0}
        assert {m["name"] for m in cell.per_layer} >= set(PER_LAYER[name])
        for metric in PER_LAYER[name]:
            assert name in listed[metric]["workloads"], metric
            assert listed[metric]["moves"] == MOVES[name], metric
            # what a per-layer metric moves, its cell reports
            assert name in ends[MOVES[name]]["workloads"]
        reported = {m["name"] for m in cell.end_to_end}
        assert {"setup_s", MOVES[name]} <= reported
    # in neither is items_per_s listed: a 30 s rate spread by 1.40 % in the
    # windowed cell's first set (PERF.md section 2)
    for name in (PER_CALL, WINDOWED):
        assert {m["name"] for m in cells.load_cell(name).end_to_end} == {
            MOVES[name], "setup_s"}
    per_call = cells.load_cell(PER_CALL)
    assert (per_call.params["loop"], per_call.params["view_cycle"],
            per_call.params["iterations_per_call"]) == ("per_call", 4, 1)
    windowed = cells.load_cell(WINDOWED)
    assert (windowed.params["loop"], windowed.params["view_cycle"],
            windowed.params["iterations_per_call"]) == ("window", 1, 32)
    assert per_call.params["n"] == windowed.params["n"] == 2048 * 2048
    # at most two of the benchmark's cells in four ask for four chips
    four = [w["name"] for w in man["workloads"] if w["chips"] == 4]
    assert len(four) <= len(man["workloads"]) // 2
