"""``resync_dispatches_per_call`` held to a trace made by hand (CPU container,
no chip: ``python3 -m pytest benchmark/checks/test_resync_dispatches.py -q``).

The trace, in microseconds: the caller's thread and a window 1000-4000 of
three calls.  ``ck/resync`` instants tagged ``part:lane``, one a lane where a
flush ends its issue, with the dispatches the lane was handed:

    win 1  900-905     4 lanes x 1   before the window: not counted
    win 2  1100-1106   4 lanes x 1   call 1's range move: 4
    win 3  (none)                    call 2's ranges stood still: no flush
    win 4  3100-3106   lanes 0, 1, 3 x 1 and lane 2 x 2 (two dispatches a
                       lane, as a lane with no batched entry would read): 5
    win 5  4100-4103   4 lanes x 1   ``flush()`` after the window: not counted

(4 + 5) / 2 flushes = 4.5; per flush, not per call: call 2 is not in the
denominator.  The marks need no chip's timeline: the reader takes the window
from ``ctx.reduced`` and nothing else of it.
"""

import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import cells  # noqa: E402
import host_phases  # noqa: E402
import xplane  # noqa: E402

METRIC = "resync_dispatches_per_call"
US = 1e-6


def ev(name, start, dur=0.0, **stats):
    return SimpleNamespace(name=name, start_ns=start * 1e3,
                           duration_ns=dur * 1e3, stats=list(stats.items()))


def lane_marks(at, win, dispatches=(1, 1, 1, 1)):
    return [ev("ck/resync", at + 1.5 * lane, 1.0, tag="part:lane", lane=lane,
               win=win, dispatches=d, pieces=2 * d, bytes=4 << 20,
               issue_us=500.0)
            for lane, d in enumerate(dispatches)]


def profile(marks=True):
    caller = (
        [ev("bench/call", 1000 + 1000 * k, 1000) for k in range(3)]
        + [ev("ck/resync", 1050, 200, tag="range-move", win=2),
           ev("ck/resync", 1060, 1.0, tag="part:issue", win=2),
           ev("ck/resync", 1110, 1.0, tag="part:join", win=2),
           ev("ck/resync", 3050, 200, tag="range-move", win=4),
           ev("ck/resync", 4090, 100, tag="flush", win=5)]
        + (lane_marks(900, 1) + lane_marks(1100, 2)
           + lane_marks(3100, 4, (1, 1, 2, 1)) + lane_marks(4100, 5)) * marks)
    return SimpleNamespace(planes=[SimpleNamespace(
        name=xplane.HOST_PLANE,
        lines=[SimpleNamespace(name="python3", events=caller)])])


def read_from(prof, monkeypatch):
    """The reader as a traced run calls it, the run's trace being ``prof``."""
    monkeypatch.setattr(xplane, "find_xplane", lambda _dir: "by-hand")
    monkeypatch.setattr(xplane, "_profile", lambda _path: prof)
    ctx = SimpleNamespace(reduced=SimpleNamespace(
        t0=1000 * US, t1=4000 * US, calls=3))
    return cells.load_reader(METRIC).read(ctx)


def test_reader_returns_the_hand_computed_value(monkeypatch):
    assert read_from(profile(), monkeypatch) == pytest.approx(4.5)


def test_only_the_marks_inside_the_window_count():
    reader = cells.load_reader(METRIC)
    lines = host_phases.host_lines(profile())
    assert reader.dispatches(lines, 1000 * US, 4000 * US) == (9, 2)
    assert reader.dispatches(lines, 0.0, 5000 * US) == (17, 4)
    assert reader.dispatches(lines, 1000 * US, 2000 * US) == (4, 1)
    assert reader.dispatches(lines, 2000 * US, 3000 * US) == (0, 0)


def test_reader_leaves_the_metric_out_without_the_mark(monkeypatch):
    """A parent commit cuts ``ck/resync`` into its four parts and marks no
    lane: nothing to read, and the reader does not raise."""
    assert read_from(profile(marks=False), monkeypatch) is None
    assert read_from(SimpleNamespace(planes=[]), monkeypatch) is None


def test_the_metric_is_listed_with_its_reader():
    (entry,) = [m for m in cells.manifest()["per_layer"]
                if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "transfers",
        "moves": "items_per_s.balanced",
        "workloads": ["mandelbrot_balance_4chip"]}
    cell = cells.load_cell("mandelbrot_balance_4chip")
    assert METRIC in [m["name"] for m in cell.per_layer]
    # beside the flush's other two readers, which it leaves where they were
    names = [m["name"] for m in cells.manifest()["per_layer"]]
    assert (names.index("resync_locks_idle_ms_per_call")
            < names.index("resync_issue_idle_ms_per_call")
            < names.index(METRIC))
