"""``window_edge`` (the parts of ``ck/fence``, ``ck/resync`` and the per-call
``ck/enqueue``, the causal check of the trace's two clocks, the edge
durations on the host's clock) and its seven readers, held to a trace made by
hand (CPU container, no chip: ``python3 -m pytest
benchmark/checks/test_window_edge.py -q``).  Nothing here yields a device
number.

The trace, in microseconds: two chips, the caller's thread (line 0) and a
thread for each lane (lines 1, 2), a window 1000-3000 of two calls.  Chip 1
(lane 1) runs 700-900 (the call before the window), 1200-1500, a readback's
slice 2150-2160, 2300-2700 and 3100-3200 (the call after it), so it is the
worst chip, idle in four gaps of 200 + 650 + 140 + 300 = 1290 us.

    before   ck/fence 850-980 (wait 855, lane 1 retired 900, feed 940,
             close 960)
    call 1   ck/enqueue 1020-1300, a per-call compute that exchanges:
             ck/schedule 1030-1050, stage 1060, submit 1100, join 1120, note
             1280; lane 1's ck/halo 1130-1150 and ck/launch 1150-1190, its
             phase-done 1200; lane 0's launch 1125-1270 (not this chip's);
             a deferred ck/enqueue 1350-1360; the barrier's ck/fence
             1620-1900 (wait 1630, lane 1 retired 1700, lane 0 1820, feed
             1830, close 1870)
    call 2   ck/enqueue 2010-2300 after a range move: ck/schedule 2020-2040,
             ck/resync 2050-2250 (locks 2060, issue 2100, join 2170, reset
             2240; lane 1's ck/download 2180-2230 inside it), submit 2260,
             join 2270, note 2295; lane 1's ck/launch 2275-2290, phase-done
             2292; ck/fence 2520-2950 (wait 2530, lane 1 retired 2800, feed
             2850, close 2900)
    after    ck/enqueue 3010-3100, lane 1's ck/launch 3050-3060

Worked out on paper, each stretch of a gap going to the innermost span as
``host_phases`` has it and, under a marked span, to the part it lies in:

    gap 1000-1200   no span 20; enqueue head 10 + 10, schedule 20, stage 40,
                    submit 20, join 10 + 10 (around lane 1's halo 20 and
                    launch 40; its phase ends at 1200)
    gap 1500-2150   no span 120 + 110; fence head 10, wait 70, wait:retired
                    130, feed 40, close 30; enqueue head 10 + 10, schedule
                    20; resync head 10, locks 40, issue 50
    gap 2160-2300   resync issue 10, join 10 + 10, download 50, reset 10;
                    enqueue head 10, submit 10, join 5 + 2 (around the launch
                    15), join:done 3, note 5
    gap 2700-3000   fence wait 100, wait:retired 50, feed 50, close 50; no
                    span 50

The edges (a barrier's retired instant R, the next site N that hands the chip
anything, the chip's gap that holds the stretch): R 900, N 1060 (the stage
mark), gap 900-1200: -140 <= d <= 0; R 1700, N 2100 (the issue mark), gap
1500-2150: -50 <= d <= 200; R 2800, N 3050, gap 2700-3100: -50 <= d <= 100.
Together -50 <= d <= 0: causal as it stands, slack 50 us.
"""

import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import cells  # noqa: E402
import host_phases  # noqa: E402
import window_edge  # noqa: E402
import xplane  # noqa: E402

US = 1e-6
FENCE, RESYNC, ENQUEUE = (window_edge.FENCE, window_edge.RESYNC,
                          window_edge.ENQUEUE)
#: (kind, part) -> idle microseconds, from the table above
PARTS = {
    (FENCE, "head"): 10, (FENCE, "wait"): 170, (FENCE, "wait:retired"): 180,
    (FENCE, "feed"): 90, (FENCE, "close"): 80,
    (RESYNC, "head"): 10, (RESYNC, "locks"): 40, (RESYNC, "issue"): 60,
    (RESYNC, "join"): 20, (RESYNC, "reset"): 10,
    (ENQUEUE, "head"): 50, (ENQUEUE, "stage"): 40, (ENQUEUE, "submit"): 30,
    (ENQUEUE, "join"): 27, (ENQUEUE, "join:done"): 3, (ENQUEUE, "note"): 5,
    (ENQUEUE, "unmarked"): 0}
#: metric -> value per call (two calls), milliseconds or microseconds
BY_HAND = {
    "barrier_tail_ms_per_call": 0.175,       # 1900 - 1700 and 2950 - 2800
    "window_head_ms_per_call": 0.1975,       # 1150 - 1020 and 2275 - 2010
    "trace_clock_violation_us": 0.0,
    "resync_locks_idle_ms_per_call": 0.020,
    "resync_issue_idle_ms_per_call": 0.030,
    "enqueue_stage_idle_ms_per_call": 0.020,
    "enqueue_join_idle_ms_per_call": 0.015}  # join 27 + join:done 3
VARIANTS = ["barrier_tail_ms_per_call.balanced",
            "window_head_ms_per_call.balanced",
            "trace_clock_violation_us.balanced"]
ONE_CHIP = ["nbody_8k_window", "nbody_32k_window", "mvt_16k_window"]
FOUR_CHIPS = ["mandelbrot_balance_4chip", "wave_halo_4chip"]


def ev(name, start, dur=0.0, **stats):
    return SimpleNamespace(name=name, start_ns=start * 1e3,
                           duration_ns=dur * 1e3, stats=list(stats.items()))


def plane(name, *lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=n, events=events) for n, events in lines])


def profile(shift=0.0, marks=True, mark_us=0.0):
    """The trace above; ``shift`` moves the DEVICE's timeline (both chips),
    ``marks`` False leaves every mark out (a parent commit's trace),
    ``mark_us`` gives a mark the microsecond or two it has in a real one."""
    def mark(kind, at, tag, **stats):
        return [ev("ck/" + kind, at, mark_us, tag=tag, **stats)] * marks

    def ops(*busy):
        return ("XLA Ops", [ev("%k.1 = f32[8]{0} custom-call(f32[8]{0} %p)",
                               a + shift, b - a) for a, b in busy])

    def barrier(start, end, wait, feed, close, win):
        return ([ev("ck/fence", start, end - start, tag="barrier", win=win)]
                + mark("fence", wait, "part:wait", win=win)
                + mark("fence", feed, "part:feed", win=win)
                + mark("fence", close, "part:close", win=win))

    caller = (
        barrier(850, 980, 855, 940, 960, win=1)
        + [ev("bench/call", 1000, 1000), ev("bench/enqueue", 1000, 600),
           ev("bench/barrier", 1600, 400),
           ev("ck/enqueue", 1020, 280, tag="k", win=2, cid=7),
           ev("ck/schedule", 1030, 20, win=2, cid=7)]
        + mark("engage", 1060, "part:stage", win=2, cid=7)
        + mark("engage", 1100, "part:submit", win=2, cid=7)
        + mark("engage", 1120, "part:join", win=2, cid=7)
        + mark("engage", 1280, "part:note", win=2, cid=7)
        + [ev("ck/enqueue", 1350, 10, tag="k fused-defer", win=2, cid=7)]
        + barrier(1620, 1900, 1630, 1830, 1870, win=2)
        + [ev("bench/call", 2000, 1000), ev("bench/enqueue", 2000, 500),
           ev("bench/barrier", 2500, 500),
           ev("ck/enqueue", 2010, 290, tag="k", win=3, cid=7),
           ev("ck/schedule", 2020, 20, win=3, cid=7),
           ev("ck/resync", 2050, 200, tag="range-move", win=3, cid=7)]
        + mark("resync", 2060, "part:locks", win=3)
        + mark("resync", 2100, "part:issue", win=3)
        + mark("resync", 2170, "part:join", win=3)
        + [ev("ck/download", 2180, 50, lane=1, tag="out", win=3)]
        + mark("resync", 2240, "part:reset", win=3)
        + mark("engage", 2260, "part:submit", win=3, cid=7)
        + mark("engage", 2270, "part:join", win=3, cid=7)
        + mark("engage", 2295, "part:note", win=3, cid=7)
        + barrier(2520, 2950, 2530, 2850, 2900, win=3)
        + [ev("ck/enqueue", 3010, 90, tag="k", win=4, cid=7)])
    lane0 = (mark("fence", 905, "retired", lane=0, win=1)
             + [ev("ck/launch", 1125, 145, lane=0, tag="k x1", win=2,
                   queued_us=500.0)]
             + mark("enqueue", 1275, "phase-done", lane=0, win=2, cid=7)
             + mark("fence", 1820, "retired", lane=0, win=2)
             + mark("fence", 2805, "retired", lane=0, win=3))
    lane1 = (mark("fence", 900, "retired", lane=1, win=1)
             + [ev("ck/halo", 1130, 20, lane=1, tag="d2d", win=2, bytes=64,
                   queued_us=30.0),
                ev("ck/launch", 1150, 40, lane=1, tag="k x1", win=2,
                   queued_us=30.0)]
             + mark("enqueue", 1200, "phase-done", lane=1, win=2, cid=7)
             + mark("fence", 1700, "retired", lane=1, win=2)
             + [ev("ck/launch", 2275, 15, lane=1, tag="k x1", win=3,
                   queued_us=20.0)]
             + mark("enqueue", 2292, "phase-done", lane=1, win=3, cid=7)
             + mark("fence", 2800, "retired", lane=1, win=3)
             + [ev("ck/launch", 3050, 10, lane=1, tag="k x1", win=4,
                   queued_us=10.0)])
    return SimpleNamespace(planes=[
        plane("/device:TPU:0", ops((1100, 1900), (2100, 2900))),
        plane("/device:TPU:1", ops((700, 900), (1200, 1500), (2150, 2160),
                                   (2300, 2700), (3100, 3200))),
        plane("/host:CPU", ("python3", caller), ("python3", lane0),
              ("python3", lane1))])


def reduced_of(prof) -> xplane.Reduced:
    devices, spans = {}, []
    for p in prof.planes:
        m = xplane.DEVICE_PLANE.match(p.name)
        for line in p.lines:
            for e in line.events:
                a = e.start_ns * 1e-9
                b = a + e.duration_ns * 1e-9
                if m is not None:
                    devices.setdefault(int(m.group(1)), []).append(
                        xplane.Op("k.1", "custom-call", a, b))
                elif e.name.startswith("bench/"):
                    spans.append(xplane.Span(e.name, a, b))
    return xplane.reduce(xplane.Trace(devices, sorted(
        spans, key=lambda s: s.start)))


def edge_of(**how) -> window_edge.Edge:
    prof = profile(**how)
    return window_edge.reduce(prof, reduced_of(prof))


def phases_of(**how) -> host_phases.Phases:
    prof = profile(**how)
    return host_phases.reduce(prof, reduced_of(prof))


@pytest.fixture(scope="module")
def edge():
    e = edge_of()
    assert (e.chip, e.lane, e.calls) == (1, 1, 2)
    return e


def in_us(parts: dict) -> dict:
    return {k: pytest.approx(v * US, abs=1e-12) for k, v in parts.items()}


def in_order(names: list, wanted: list) -> bool:
    """``wanted`` is listed in ``names`` in that order, whatever a later PR
    puts between or behind them."""
    rest = iter(names)
    return all(w in rest for w in wanted)


def test_a_span_cut_at_its_marks_gives_each_gap_to_the_right_part(edge):
    assert edge.idle_s == pytest.approx(1290 * US)
    assert edge.parts_raw == in_us(PARTS)
    assert edge.parts == in_us(PARTS)  # causal as it stands: no shift


def test_the_parts_of_a_span_add_up_to_what_host_phases_gives_the_span(edge):
    phases = phases_of()
    assert phases.by_kind == in_us({
        "ck/fence": 530, "ck/resync": 140, "ck/download": 50,
        "ck/enqueue": 155, "ck/schedule": 40, "ck/halo": 20, "ck/launch": 55,
        host_phases.UNNAMED: 300})
    for kind in (FENCE, RESYNC, ENQUEUE):
        parts = sum(v for (k, _part), v in edge.parts_raw.items()
                    if k == kind)
        assert parts == pytest.approx(phases.by_kind[kind])
    # and the groups of the print are host_phases's own
    groups = edge.groups[0.0]
    for group in list(host_phases.GROUPS) + [host_phases.DISPATCH]:
        assert groups.get(group, 0.0) == pytest.approx(phases.group_s(group))
    assert groups[host_phases.UNNAMED] == pytest.approx(300 * US)


@pytest.mark.parametrize("mark_us", [0.0, 2.0])
def test_accepted_readers_read_what_they_read_without_the_marks(mark_us):
    """One trace reduced with and without the marks: every number the
    accepted readers take from ``host_phases`` is the same.  A mark of a
    real trace is a microsecond or two long and takes that much of a gap
    it lies in: under its own span's kind (``ck/fence``, ``ck/resync``) or,
    for the per-call path's four, under ``ck/engage``, which is dispatch as
    ``ck/enqueue`` is: the groups do not move.  A ``ck/enqueue`` mark on the
    caller's thread would count as a compute."""
    plain, marked = phases_of(marks=False), phases_of(mark_us=mark_us)
    assert marked.idle_s == pytest.approx(plain.idle_s)
    for group in list(host_phases.GROUPS) + [host_phases.DISPATCH]:
        assert marked.group_s(group) == pytest.approx(plain.group_s(group))
    assert marked.by_kind[host_phases.UNNAMED] == pytest.approx(
        plain.by_kind[host_phases.UNNAMED])
    assert marked.unfused_computes == plain.unfused_computes == 2
    assert marked.queue_wait_s == pytest.approx(plain.queue_wait_s)
    assert plain.queue_wait_s == pytest.approx(50 * US)  # 30 + 20, lane 1
    if not mark_us:
        assert marked.by_kind == pytest.approx(plain.by_kind)


def test_a_causal_trace_reads_no_violation_and_its_slack(edge):
    assert edge.edges == 3
    assert edge.shift_s == 0.0
    assert edge.lower_s == pytest.approx(-50 * US)
    assert edge.upper_s == pytest.approx(0.0, abs=1e-12)
    assert edge.slack_s == pytest.approx(50 * US)
    # what the edges inside the window measure, at that shift
    assert edge.wake_s == pytest.approx((200 + 100) * US)
    assert edge.start_s == pytest.approx((50 + 50) * US)


@pytest.mark.parametrize("shift, least", [(800.0, -800.0), (-300.0, 250.0)])
def test_a_shifted_device_timeline_reads_its_shift_and_the_same_split(
        edge, shift, least):
    """The device's timeline 0.8 ms late reads 800 us, and shifted back by
    that the split is the unshifted trace's.  0.3 ms early, the least shift
    that is causal is the near end of the interval, 250 us: the split then
    differs from the unshifted by the slack's 50 us an edge, no more."""
    moved = edge_of(shift=shift)
    assert moved.chip == 1 and moved.edges == 3
    assert moved.shift_s == pytest.approx(least * US)
    assert moved.slack_s == pytest.approx(50 * US)
    read = cells.load_reader("trace_clock_violation_us").read
    assert read(SimpleNamespace(window_edge=moved)) == pytest.approx(
        abs(least))
    if shift + least == 0:
        assert moved.parts == in_us(PARTS)
        assert moved.groups[moved.shift_s] == pytest.approx(edge.groups[0.0])
    else:
        off = sum(abs(moved.parts[k] - edge.parts[k]) for k in PARTS)
        assert 0 < off <= 2 * 4 * 50 * US
    # on the trace's own clocks the split is another
    assert moved.parts_raw != in_us(PARTS)


@pytest.mark.parametrize("shift", [800.0, -300.0, 37.0])
def test_host_clock_durations_do_not_move_under_any_shift(edge, shift):
    moved = edge_of(shift=shift)
    assert moved.tail_s == pytest.approx(edge.tail_s) == pytest.approx(
        350 * US)
    assert moved.tail_parts == in_us(
        {"wait:retired": 180, "feed": 90, "close": 80})
    assert moved.head_s == pytest.approx(edge.head_s) == pytest.approx(
        395 * US)
    assert moved.between_s == pytest.approx(edge.between_s) == pytest.approx(
        110 * US)


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_reader_returns_the_hand_computed_value(metric, edge):
    ctx = SimpleNamespace(window_edge=edge)
    assert cells.load_reader(metric).read(ctx) == pytest.approx(
        BY_HAND[metric], abs=1e-9)


@pytest.mark.parametrize("metric", sorted(BY_HAND) + VARIANTS)
def test_a_trace_without_marks_reads_none_everywhere(metric):
    """A parent commit's program writes no mark: the reduction is None and
    no reader raises."""
    assert edge_of(marks=False) is None
    assert cells.load_reader(metric).read(
        SimpleNamespace(window_edge=None)) is None


def test_a_part_no_span_was_cut_into_reads_none(edge):
    """A cell without a range move has no ``locks``; one without an exchange
    no ``stage``: nothing to read, not 0."""
    bare = edge._replace(parts={k: v for k, v in edge.parts.items()
                                if k[0] == FENCE})
    ctx = SimpleNamespace(window_edge=bare)
    for metric in sorted(BY_HAND)[1:5]:
        assert cells.load_reader(metric).read(ctx) is None
    assert cells.load_reader("barrier_tail_ms_per_call").read(
        ctx) == pytest.approx(0.175)


def test_one_lane_barrier_has_no_feed_and_a_late_phase_is_all_running():
    span = host_phases.HostSpan("ck/fence", 10.0, 20.0, 0, {"tag": "barrier"})

    def mark(at, part):
        return host_phases.HostSpan("ck/fence", at, at, 0,
                                    {"tag": "part:" + part})

    one = window_edge.pieces(span, [mark(11.0, "wait"), mark(18.0, "close")],
                             anchor=17.0)
    assert one == [("head", 10.0, 11.0), ("wait", 11.0, 17.0),
                   ("wait:retired", 17.0, 18.0), ("close", 18.0, 20.0)]
    assert window_edge.pieces(span, []) == [("unmarked", 10.0, 20.0)]
    call = span._replace(name="ck/enqueue")
    marks = [mark(12.0, "submit"), mark(13.0, "join"), mark(19.0, "note")]
    # the lane's phase ended before the caller began to wait: all of it done
    assert window_edge.pieces(call, marks, anchor=12.5)[2] == (
        "join:done", 13.0, 19.0)
    # after the caller stopped waiting (an error path): all of it running
    assert window_edge.pieces(call, marks, anchor=19.5)[2] == (
        "join", 13.0, 19.0)


def test_the_least_shift_is_one_that_a_gap_of_every_edge_allows():
    """Edge by edge the nearest gap can be the wrong one: the second edge's
    nearest allows +100..+500, and only its other gap agrees with the
    first edge's."""
    bounds = [[(-940.0, -800.0)], [(100.0, 500.0), (-850.0, -700.0)]]
    assert window_edge.least_shift(bounds) == (
        -800.0, [(-940.0, -800.0), (-850.0, -700.0)])
    assert window_edge.least_shift([[(-5.0, 3.0)], [(-1.0, 9.0)]]) == (
        0.0, [(-5.0, 3.0), (-1.0, 9.0)])
    # no shift serves both: the middle of what their nearest pairs leave
    assert window_edge.least_shift([[(1.0, 2.0)], [(3.0, 4.0), (9.0, 9.5)]]
                                   ) == (2.5, [(1.0, 2.0), (3.0, 4.0)])


def test_a_trace_no_shift_makes_causal_reads_the_least_wrong_one():
    """An operation that ends 0.4 ms after its barrier saw the chip retired
    and one that starts 0.3 ms before its dispatch, in ONE session."""
    prof = profile()
    chip1 = prof.planes[1].lines[0].events
    chip1[1].duration_ns += 600e3   # 1200-2100: ends after R 1700
    chip1[3].start_ns -= 200e3      # 2100-2700 ... and the gap before it
    chip1[2].start_ns = 2099e3      # closes: the slice rides the busy run
    chip1[4].start_ns -= 350e3      # 2750: before the launch at 3050 opens
    e = window_edge.reduce(prof, reduced_of(prof))
    assert e.lower_s > e.upper_s and e.slack_s < 0
    assert e.shift_s == pytest.approx(0.5 * (e.lower_s + e.upper_s))


def test_the_report_names_what_the_issue_asks_to_print(edge):
    text = window_edge.report(edge)
    for said in ("trace_clock_violation_us 0.0", "slack 0.050 ms",
                 "barrier_tail 0.175", "'wait:retired': 0.09",
                 "window_head 0.19", "next ck/enqueue 0.055",
                 "wake-up 0.150", "dispatch-to-start 0.050",
                 "'join:done': 0.00"):
        assert said in text, (said, text)


def test_the_ten_entries_are_listed_with_their_readers():
    listed = {m["name"]: m for m in cells.manifest()["per_layer"]}
    layers = {"barrier_tail_ms_per_call": "scheduler / balancer",
              "window_head_ms_per_call": "fused dispatch",
              "trace_clock_violation_us": "device"}
    for metric, layer in layers.items():
        entry, variant = listed[metric], listed[metric + ".balanced"]
        assert entry["workloads"] == ONE_CHIP
        assert entry["moves"] == "call_p50_ms"
        assert variant["workloads"] == FOUR_CHIPS
        assert variant["moves"] == "items_per_s.balanced"
        unit, source = (("us", "device_trace") if metric.endswith("_us")
                        else ("ms", "program_span"))
        for m in (entry, variant):
            assert (m["unit"], m["source"], m["layer"], m["better"]) == (
                unit, source, layer, "lower")
        # a variant has no file of its own: its quantity's reader reads it
        assert cells.load_reader(metric + ".balanced").__file__.endswith(
            os.path.join("layer_metrics", metric + ".py"))
    for metric, cell, layer in [
            ("resync_locks_idle_ms_per_call", FOUR_CHIPS[0], "transfers"),
            ("resync_issue_idle_ms_per_call", FOUR_CHIPS[0], "transfers"),
            ("enqueue_stage_idle_ms_per_call", FOUR_CHIPS[1],
             "fused dispatch"),
            ("enqueue_join_idle_ms_per_call", FOUR_CHIPS[1],
             "fused dispatch")]:
        m = listed[metric]
        assert m["workloads"] == [cell] and m["layer"] == layer
        assert (m["unit"], m["source"], m["moves"], m["better"]) == (
            "ms", "device_trace", "items_per_s.balanced", "lower")
        assert cells.load_reader(metric).__file__.endswith(metric + ".py")
    new = list(BY_HAND) + VARIANTS
    assert set(new) <= set(listed) and len(new) == 10
    # no name ends in .nbody (checks/test_nbody_starts.py holds those lists),
    # and the cell whose chip idles 0.14 % of its window gets none
    assert not [n for n in new if n.endswith(".nbody")]
    spmv = [m["name"] for m in
            cells.load_cell("spmv_hpcg256_window").per_layer]
    assert not set(spmv) & set(new)


#: the wave cell's list as ``test_wave_cell.py`` holds it (by name since PR 49;
#: with ``==`` until then), held here by name too, whatever a later PR appends
WAVE_ACCEPTED = [
    "halo_idle_ms_per_call", "halo_bytes_per_step", "halo_host_hops",
    "wave_kernel_ms_per_iter", "wave_roofline", "device_idle_share.wave",
    "window_compiles.wave", "xla_launch_share.wave", "balance_moves.wave",
    "resync_idle_ms_per_call.wave", "dispatch_idle_ms_per_call.wave",
    "fence_idle_ms_per_call.wave", "unfused_computes_per_call.wave",
    "lane_imbalance.wave"]


def test_the_wave_cell_reports_what_it_reported_and_the_new_five():
    man = cells.manifest()
    cell = "wave_halo_4chip"
    row = next(w for w in man["workloads"] if w["name"] == cell)
    assert (row["chips"], row["config"], row["traffic"]) == (
        4, "wave_membrane", cell)
    conf = next(c for c in man["configs"] if c["name"] == "wave_membrane")
    assert conf["reduced"] == [] and len(conf["source"]) <= 200
    assert len(row["why"]) <= 200 and len(conf["why"]) <= 200
    listed = {m["name"]: m for m in man["per_layer"]}
    assert all(listed[m]["workloads"] == [cell]
               and listed[m]["moves"] == "items_per_s.balanced"
               for m in WAVE_ACCEPTED)
    loaded = cells.load_cell(cell)
    assert [m["name"] for m in loaded.end_to_end] == [
        "items_per_s.balanced", "setup_s"]
    names = [m["name"] for m in loaded.per_layer]
    assert names[:len(WAVE_ACCEPTED)] == WAVE_ACCEPTED
    assert in_order(names[len(WAVE_ACCEPTED):], VARIANTS + [
        "enqueue_stage_idle_ms_per_call", "enqueue_join_idle_ms_per_call"])
    assert loaded.cfg["source"] == conf["source"]
    assert loaded.cfg["width"] * loaded.cfg["height"] == loaded.params["n"]
    # the other four-chip cell reports what it reported, and its five
    mandel = [m["name"] for m in
              cells.load_cell("mandelbrot_balance_4chip").per_layer]
    assert not set(mandel) & set(WAVE_ACCEPTED)
    assert in_order(mandel, VARIANTS + ["resync_locks_idle_ms_per_call",
                                        "resync_issue_idle_ms_per_call"])


@pytest.mark.parametrize("name", ONE_CHIP)
def test_the_one_chip_cells_report_the_three_behind_what_they_reported(name):
    names = [m["name"] for m in cells.load_cell(name).per_layer]
    assert in_order(names, ["barrier_tail_ms_per_call",
                            "window_head_ms_per_call",
                            "trace_clock_violation_us"])
    assert "call_p50_ms" in [m["name"] for m in
                             cells.load_cell(name).end_to_end]
