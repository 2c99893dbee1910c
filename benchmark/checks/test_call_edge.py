"""``call_edge`` (a synchronous ``compute()`` cut into parts on the host's
clock, the causal check and the scale of a session without a barrier) and its
reader, held to a trace made by hand (CPU container, no chip: ``python3 -m
pytest benchmark/checks/test_call_edge.py -q``).  Nothing here yields a device
number.

The trace, in microseconds: one chip (lane 0), the caller's thread (line 0),
the lane's pool thread (line 1) and its stream driver (line 2); three calls,
half a second apart so that the slack is small beside the session's length,
as it is in a real one.  ``B`` is a call's base (0, 500 000, 1 000 000).

    call 1   bench/call B+1000..2000, ONE monolithic compute (win 1):
             ck/enqueue 1010-1960 (submit 1050, join 1060, note 1940); the
             lane: phase-start 1080, phase-locked 1090, ck/upload 1100-1140,
             ck/launch 1150-1230 (part:call 1170, part:handed 1210),
             part:issued 1240, ck/download 1250-1900 (part:landed 1850),
             phase-done 1920; the chip runs 1300-1800
    call 2   bench/call B+2100..3600, a STREAMED compute (win 2): ck/enqueue
             2110-3560 (submit 2140, join 2150, note 3540); phase-start 2160,
             phase-locked 2165, ck/upload-chunk 2170-2190; on the stream
             driver four ck/launch 2200-2260, 2400-2450, 2600-2650, 2800-2850
             (part:call 2215 / 2410 / 2610 / 2810, part:handed 2250 / 2440 /
             2640 / 2840), each followed by its part:issued; on the pool
             thread four ck/download-chunk 2900-2960, 2970-3030, 3100-3180,
             3300-3480 (part:landed 2940, 3000, 3150, 3400), phase-done
             3500; the chip runs 2300-2500, 2500-2750, 2750-3000, 3000-3350
    call 3   bench/call B+3700..5200, TWO computes: A (win 3) ck/enqueue
             3720-4300 (submit 3740, join 3745, note 4290), phase-start 3760,
             phase-locked 3762, ck/launch 3780-3840 (part:call 3790,
             part:handed 3830), part:issued 3850, ck/download 3860-4270
             (part:landed 4250), phase-done 4280, the chip runs 3900-4200;
             then the harness's own 100 us; B (win 4) launches NOTHING
             (no_compute_mode): ck/enqueue 4400-5150 (submit 4420, join 4425,
             note 5140), phase-start 4440, phase-locked 4445, part:issued
             4470, ck/download 4480-5120 (part:landed 5000), phase-done 5130;
             the chip (the copy) runs 4600-4900

Worked out on paper, a call's parts (they add up to its bench/call):

             open caller hop lock lane prepare admit  run copy wrap join note return
    call 1     10     40  30   10   60      20    40  640   50   20   20   20     40
    call 2     10     30  20    5   35      15    35 1150   80   20   40   20     40
    call 3     20     40  40    7   43      10    40  950  140   20   20   20    150

(call 3 = A: 20 20 2 18 10 40 420 20 10 10 10 100, and B: 20 20 5 25 0 0 530
120 10 10 10 50).  Everything outside ``run`` and ``open``: 350, 340, 530; the
median call reads 350.  The edges (a compute's last part:landed R, the next
one's first dispatch site N, the gap that holds the stretch): R 1850, N
502170 (the upload-chunk's open), gap 1800-502300: -130 <= d <= 50; R 503400,
N 1003790 (part:call), gap 503350-1003900: -110 <= d <= 50; R 1004250, N
1004445 (B hands the chip nothing before its lock), gap 1004200-1004600: -155
<= d <= 50.  Together -110 <= d <= 50: causal as it stands, slack 160 us.
"""

import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import call_edge  # noqa: E402
import cells  # noqa: E402
import host_phases  # noqa: E402
import xplane  # noqa: E402

US = 1e-6
NAME = "call_edge_ms_per_call"
CELLS = ["reduce_1gib_percall_1chip", "md_lj_step_1chip"]
#: per-call cells that print the line by hand and list no entry
BY_HAND_ONLY = ["bfs_1m_traversal_1chip", "mandelbrot_percall_1chip"]
ORDER = (call_edge.OPEN,) + call_edge.PARTS
#: call -> its parts in ``ORDER``, microseconds (the table above)
BY_HAND = [
    (10, 40, 30, 10, 60, 20, 40, 640, 50, 20, 20, 20, 40),
    (10, 30, 20, 5, 35, 15, 35, 1150, 80, 20, 40, 20, 40),
    (20, 40, 40, 7, 43, 10, 40, 950, 140, 20, 20, 20, 150)]
MEDIANS = dict(zip(ORDER, (10, 40, 30, 7, 43, 15, 40, 950, 80, 20, 20, 20,
                           40)))
BASES = (0, 500_000, 1_000_000)


def ev(name, start, dur=0.0, **stats):
    return SimpleNamespace(name=name, start_ns=start * 1e3,
                           duration_ns=dur * 1e3, stats=list(stats.items()))


def profile(scale=1.0, shift=0.0, marks=True, slow=0.0, mark_us=0.0):
    """The trace above.  The DEVICE's line is laid down as ``scale * t +
    shift``; ``marks`` False leaves ISSUE 52's marks out (a parent commit's
    trace); ``slow`` lets the bytes of call 1 land that much later;
    ``mark_us`` gives an instant the microsecond or two it has in a real
    trace."""
    def mark(kind, at, tag, **stats):
        return [ev("ck/" + kind, at, mark_us, tag=tag, **stats)]

    def new(kind, at, tag, **stats):
        return mark(kind, at, tag, **stats) * marks

    def launch(b, a, call, handed, end, win):
        return ([ev("ck/launch", b + a, end - a, lane=0, tag="k x1", win=win,
                    cid=7)]
                + new("engage", b + call, "part:call", lane=0, win=win, cid=7)
                + new("engage", b + handed, "part:handed", lane=0, win=win,
                      cid=7))

    def download(b, kind, a, landed, end, win):
        return ([ev("ck/" + kind, b + a, end - a, lane=0, tag="out", win=win)]
                + mark(kind, b + landed, "part:landed", lane=0, win=win))

    def compute(b, a, submit, join, note, end, win):
        return ([ev("ck/enqueue", b + a, end - a, tag="k", win=win, cid=7),
                 ev("ck/schedule", b + a + 5, 10, win=win, cid=7)]
                + mark("engage", b + submit, "part:submit", win=win, cid=7)
                + mark("engage", b + join, "part:join", win=win, cid=7)
                + mark("engage", b + note, "part:note", win=win, cid=7))

    def phase(b, start, locked, done, win):
        return (new("enqueue", b + start, "phase-start", lane=0, win=win,
                    cid=7, hop_us=float(start))
                + new("enqueue", b + locked, "phase-locked", lane=0, win=win,
                      cid=7)
                + mark("enqueue", b + done, "phase-done", lane=0, win=win,
                       cid=7))

    b1, b2, b3 = BASES
    caller = (
        [ev("bench/call", b1 + 1000, 1000 + slow)]
        + compute(b1, 1010, 1050, 1060, 1940 + slow, 1960 + slow, win=1)
        + [ev("bench/call", b2 + 2100, 1500)]
        + compute(b2, 2110, 2140, 2150, 3540, 3560, win=2)
        + [ev("bench/call", b3 + 3700, 1500)]
        + compute(b3, 3720, 3740, 3745, 4290, 4300, win=3)
        + compute(b3, 4400, 4420, 4425, 5140, 5150, win=4))
    pool = (
        phase(b1, 1080, 1090, 1920 + slow, win=1)
        + [ev("ck/upload", b1 + 1100, 40, lane=0, tag="in", win=1, bytes=64)]
        + launch(b1, 1150, 1170, 1210, 1230, win=1)
        + mark("download", b1 + 1240, "part:issued", lane=0, win=1)
        + download(b1, "download", 1250, 1850 + slow, 1900 + slow, win=1)
        + phase(b2, 2160, 2165, 3500, win=2)
        + [ev("ck/upload-chunk", b2 + 2170, 20, lane=0, tag="in", win=2)]
        + download(b2, "download-chunk", 2900, 2940, 2960, win=2)
        + download(b2, "download-chunk", 2970, 3000, 3030, win=2)
        + download(b2, "download-chunk", 3100, 3150, 3180, win=2)
        + download(b2, "download-chunk", 3300, 3400, 3480, win=2)
        + phase(b3, 3760, 3762, 4280, win=3)
        + launch(b3, 3780, 3790, 3830, 3840, win=3)
        + mark("download", b3 + 3850, "part:issued", lane=0, win=3)
        + download(b3, "download", 3860, 4250, 4270, win=3)
        + phase(b3, 4440, 4445, 5130, win=4)
        + mark("download", b3 + 4470, "part:issued", lane=0, win=4)
        + download(b3, "download", 4480, 5000, 5120, win=4))
    driver = []
    for a, call, handed, end in ((2200, 2215, 2250, 2260),
                                 (2400, 2410, 2440, 2450),
                                 (2600, 2610, 2640, 2650),
                                 (2800, 2810, 2840, 2850)):
        driver += launch(b2, a, call, handed, end, win=2)
        driver += mark("download-chunk", b2 + end + 5, "part:issued", lane=0,
                       win=2)
    busy = [(b1 + 1300, b1 + 1800 + slow),
            (b2 + 2300, b2 + 2500), (b2 + 2500, b2 + 2750),
            (b2 + 2750, b2 + 3000), (b2 + 3000, b2 + 3350),
            (b3 + 3900, b3 + 4200), (b3 + 4600, b3 + 4900)]
    ops = [ev("%k.1 = f32[8]{0} custom-call(f32[8]{0} %p)",
              scale * a + shift, scale * (b - a)) for a, b in busy]

    def plane(name, *lines):
        return SimpleNamespace(name=name, lines=[
            SimpleNamespace(name=n, events=events) for n, events in lines])

    return SimpleNamespace(planes=[
        plane("/device:TPU:0", ("XLA Ops", ops)),
        plane("/host:CPU", ("python3", caller), ("python3", pool),
              ("python3", driver))])


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


def edge_of(**how) -> call_edge.CallEdge:
    prof = profile(**how)
    return call_edge.reduce(prof, reduced_of(prof))


@pytest.fixture(scope="module")
def edge():
    e = edge_of()
    assert (e.chip, e.lane, e.calls, e.computes, e.unanchored) == (
        0, 0, 3, 4, 0)
    return e


def in_ms(us: dict) -> dict:
    return {k: pytest.approx(v * 1e-3, abs=1e-9) for k, v in us.items()}


def cut_calls(prof) -> list:
    """The calls as ``reduce`` cuts them, by its own pieces."""
    lines = host_phases.host_lines(prof)
    ours = [s for spans in lines for s in spans if s.name.startswith("ck/")]
    out = []
    for c in (s for s in lines[0] if s.name == "bench/call"):
        spans = sorted((s for s in lines[0] if s.name == "ck/enqueue"
                        and c.start <= s.start < c.end),
                       key=lambda s: s.start)
        ends = [s.start for s in spans[1:]] + [c.end]
        out.append((c, [call_edge.cut_compute(
            e, [s for s in ours if s.stats.get("win") == e.stats["win"]],
            0, 0, nxt) for e, nxt in zip(spans, ends)]))
    return out


def test_the_parts_of_every_call_add_up_to_its_bench_call():
    for (c, computes), by_hand in zip(cut_calls(profile()), BY_HAND):
        parts = {p: sum(k.parts[p] for k in computes)
                 for p in call_edge.PARTS}
        parts[call_edge.OPEN] = computes[0].start - c.start
        assert [parts[p] for p in ORDER] == [
            pytest.approx(v * US, abs=1e-12) for v in by_hand]
        assert sum(parts.values()) == pytest.approx(c.end - c.start,
                                                    abs=1e-12)


def test_every_figure_is_the_median_of_the_calls_own_sums(edge):
    assert edge.parts_ms == in_ms(MEDIANS)
    assert edge.wall_ms == pytest.approx(1.5)
    assert edge.edge_ms == pytest.approx(0.350)       # of 350, 340, 530
    assert edge.upload_ms == pytest.approx(0.020)     # 40, 20, 0
    assert edge.after_ms == pytest.approx(0.010)      # 20, 10, 10


def test_a_streamed_compute_runs_from_its_first_handed_to_its_last_landed():
    (_c, (k,)) = cut_calls(profile())[1]
    b = BASES[1]
    assert k.stretches["admit"] == pytest.approx(((b + 2215) * US,
                                                  (b + 2250) * US))
    assert k.stretches["run"] == pytest.approx(((b + 2250) * US,
                                                (b + 3400) * US))
    assert k.stretches["copy"][1] == pytest.approx((b + 3480) * US)
    assert k.after_s == pytest.approx(10 * US)
    # the dispatch site of the causal check is the first upload's open
    assert k.site == pytest.approx((b + 2170) * US)
    assert k.called == pytest.approx((b + 2215) * US)


def test_a_call_of_several_computes_and_one_that_launches_nothing():
    (_c, (a, b)) = cut_calls(profile())[2]
    base = BASES[2]
    assert a.parts["return"] == pytest.approx(100 * US)  # the harness's own
    assert b.parts["prepare"] == b.parts["admit"] == 0.0
    assert b.stretches["run"] == pytest.approx(((base + 4470) * US,
                                                (base + 5000) * US))
    assert b.called is None
    assert b.site == pytest.approx((base + 4445) * US)  # its lock


def test_a_causal_trace_reads_scale_one_and_its_slack(edge):
    assert edge.scale == 1.0
    assert edge.raw_scale == pytest.approx(1003600 / 1003830)
    assert edge.edges == 3
    assert edge.shift_s == 0.0
    assert edge.lower_s == pytest.approx(-110 * US)
    assert edge.upper_s == pytest.approx(50 * US)
    assert edge.slack_s == pytest.approx(160 * US)
    # a call's idle is what the chip did not run of it, by part
    assert edge.idle_ms == pytest.approx(0.5)  # of 500, 450, 900
    assert edge.idle_parts_ms["run"] == pytest.approx(0.140)  # 140 100 350
    assert edge.idle_parts_ms["copy"] == pytest.approx(0.080)
    assert edge.idle_parts_ms["caller"] == pytest.approx(0.040)


@pytest.mark.parametrize("shift, least", [(800.0, -750.0), (-300.0, 190.0)])
def test_a_shifted_device_line_reads_its_shift(edge, shift, least):
    """The device's line 0.8 ms late: causal for -910..-750, the end nearest
    0 is read; 0.3 ms early: 190..350.  The slack does not move."""
    moved = edge_of(shift=shift)
    assert moved.scale == 1.0 and moved.edges == 3
    assert moved.shift_s == pytest.approx(least * US)
    assert moved.slack_s == pytest.approx(160 * US)
    # at its shift the split lies within the slack of the unshifted one
    for part in ORDER:
        assert abs(moved.idle_parts_ms[part] - edge.idle_parts_ms[part]) \
            <= 4 * 0.160


@pytest.mark.parametrize("shift", [0.0, 800.0, -2500.0])
def test_a_scaled_device_line_reads_its_scale_and_then_its_shift(edge, shift):
    """Every device duration 0.675 x the host's, and a shift besides: the
    extents give 0.67485 (short by the first dispatch-to-start and the last
    wake-up); of the stretches near it, 0.674997 is the one under which the
    three edges' bounds on the shift differ least; there the session is
    causal, whatever shift was laid on it (the pivot takes it in), with the
    160 us of slack it had."""
    moved = edge_of(scale=0.675, shift=shift)
    assert moved.raw_scale == pytest.approx(0.675 * 1003600 / 1003830)
    assert moved.scale == pytest.approx(0.675, rel=2e-5)
    assert moved.edges == 3 and moved.unserved == 0
    assert moved.lower_s <= moved.shift_s <= moved.upper_s
    assert moved.slack_s == pytest.approx(160 * US, abs=2 * US)
    for part in ORDER:
        assert abs(moved.idle_parts_ms[part] - edge.idle_parts_ms[part]) \
            <= 0.160
    # read as recorded, the same line would show the chip busy two thirds
    # as long; stretched, a call's idle is the unscaled trace's
    assert moved.idle_ms == pytest.approx(edge.idle_ms, abs=1e-3)


@pytest.mark.parametrize("how", [
    {"shift": 800.0}, {"shift": -300.0}, {"scale": 0.675},
    {"scale": 0.675, "shift": -2500.0}, {"scale": 1.3}, {"mark_us": 2.0}])
def test_the_metric_and_the_parts_move_with_neither(edge, how):
    moved = edge_of(**how)
    assert moved.edge_ms == pytest.approx(edge.edge_ms, abs=1e-12)
    assert moved.parts_ms == pytest.approx(edge.parts_ms, abs=1e-12)
    assert moved.wall_ms == pytest.approx(edge.wall_ms, abs=1e-12)
    ctx = SimpleNamespace(call_edge=moved)
    assert cells.load_reader(NAME).read(ctx) == pytest.approx(0.350)


def test_a_trace_without_the_marks_reads_none():
    """A parent commit's program writes none of ISSUE 52's marks: the
    reduction is None and the reader leaves the metric out."""
    assert edge_of(marks=False) is None
    assert cells.load_reader(NAME).read(
        SimpleNamespace(call_edge=None)) is None


def test_a_compute_that_read_nothing_back_leaves_its_call_out():
    prof = profile()
    pool = prof.planes[1].lines[1].events
    b = BASES[2]
    pool[:] = [e for e in pool if not (
        dict(e.stats).get("win") == 4 and e.name == "ck/download")]
    e = call_edge.reduce(prof, reduced_of(prof))
    assert (e.calls, e.unanchored, e.computes) == (2, 1, 2)
    assert e.edges == 1  # the two computes left are neighbours once
    assert b  # (the third call's base: its computes are gone from the cut)


def test_a_session_no_shift_makes_causal_says_which_edges_it_leaves_short():
    """Compute A's kernel starts 50 us BEFORE its ``part:call`` and ends 50
    us AFTER its bytes had landed, in one session (half a millisecond apart:
    no stretch of a clock does that): no shift serves both edges; the middle
    is read, both are 50 us short, and the line says before which compute
    of its call each lies."""
    prof = profile()
    ops = prof.planes[0].lines[0].events
    ops[5].start_ns -= 160e3      # 3740: before A's part:call 3790
    ops[5].duration_ns += 260e3   # .. to 4300: past A's R 4250
    e = call_edge.reduce(prof, reduced_of(prof))
    assert e.lower_s > e.upper_s and e.slack_s < 0
    assert e.shift_s == pytest.approx(0.5 * (e.lower_s + e.upper_s))
    assert e.unserved == 2
    assert sorted((round(v / US), nth) for v, nth, _w in e.worst) == [
        (50, 0), (50, 1)]
    assert "NOT causal: 2 edges are left short" in call_edge.report(e)
    assert edge_of().unserved == 0


def test_the_slow_calls_are_set_apart_and_the_part_that_grew_is_named():
    """Call 1's bytes land 900 us late: of three calls one is over 1.1 x
    the median wall, and ``run`` is what grew."""
    e = edge_of(slow=900.0)
    assert e.slow_calls == 1
    assert e.slow_parts_ms["run"] == pytest.approx(1.540)
    assert e.other_parts_ms["run"] == pytest.approx(1.050)  # of 1150, 950
    text = call_edge.report(e)
    assert "1 calls over 1.1 x the median wall" in text
    assert "`run` grew most" in text


def test_the_report_names_what_the_issue_asks_to_print(edge):
    text = call_edge.report(edge)
    for said in ("3 calls of 1.33 computes", "'run': 0.95", "'hop': 0.03",
                 "largest difference 0.000000 ms", "bench/call 1.500", "call_edge_ms_per_call 0.350",
                 "scale 1.00000", "3 edges", "[-0.110, 0.050] ms",
                 "slack 0.160 ms", "shifted by 0.000",
                 "0 calls over 1.1 x the median wall"):
        assert said in text, (said, text)
    scaled = call_edge.report(edge_of(scale=0.675))
    assert "THE DEVICE CLOCK IS SCALED" in scaled and "0.6750" in scaled


def test_the_entry_is_in_the_manifest_by_name_with_its_reader():
    listed = {m["name"]: m for m in cells.manifest()["per_layer"]}
    m = listed[NAME]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "ms", "lower", "program_span", "fused dispatch", "call_p50_ms")
    assert set(m["workloads"]) == set(CELLS)
    assert cells.load_reader(NAME).__file__.endswith(NAME + ".py")
    assert len(listed) <= 128


@pytest.mark.parametrize("name", CELLS)
def test_its_cells_report_the_metric_and_the_end_to_end_it_moves(name):
    cell = cells.load_cell(name)
    assert NAME in [m["name"] for m in cell.per_layer]
    assert "call_p50_ms" in [m["name"] for m in cell.end_to_end]


@pytest.mark.parametrize("name", BY_HAND_ONLY)
def test_the_other_per_call_cells_list_no_entry_and_get_the_line_by_hand(
        name):
    """``mandelbrot_percall_1chip`` moves ``call_p50_ms.percall``, another
    metric; ``bfs_1m_traversal_1chip``'s own check holds its per-layer list
    with ``==`` (``test_bfs_cell.py``), so an entry that named it would fail
    a check this PR may not edit.  ``python3 benchmark/call_edge.py
    --workload ..`` runs either traced with the reader added."""
    cell = cells.load_cell(name)
    assert NAME not in [m["name"] for m in cell.per_layer]
    assert callable(call_edge.main)
