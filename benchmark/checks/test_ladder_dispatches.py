"""``ladder_dispatches_per_call`` held to a trace made by hand (CPU container,
no chip: ``python3 -m pytest benchmark/checks/test_ladder_dispatches.py -q``).

``fixtures/ladder_dispatches.xspace.txt`` is ``host_phases.xspace.txt`` with
other tags on its ``ck/launch`` spans and one span more: chip 1 (lane 1) is
the worst chip, the window is 1000-3000 us, two calls.  Lane 1's spans:

    100-150     "mandelbrot x9"         before the window: not counted
    1060-1120   "mandelbrot x7"         call 1's per-call launch: 7
    1530-1640   "fused:mandelbrot x16"  a window dispatch (x = iterations): not counted
    2040-2260   "mandelbrot x5"         call 2's per-call launch: 5

and lane 0's "mandelbrot x3" (1400-1450) is another chip's.  (7 + 5) / 2 = 6.
In ``host_phases.xspace.txt`` itself both per-call launches read ``x1``: 1.0,
what a program reads whose multi-rung launches ride the fused executable.
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

METRIC = "ladder_dispatches_per_call"


def fixture(name: str) -> str:
    return os.path.join(HERE, "fixtures", name)


def read_from(path: str, monkeypatch):
    """The reader as a traced run calls it, with the run's trace at ``path``."""
    monkeypatch.setattr(xplane, "find_xplane", lambda _dir: path)
    reduced = xplane.reduce(xplane.load(path))
    ctx = SimpleNamespace(
        reduced=reduced,
        host_phases=host_phases.reduce(xplane._profile(path), reduced))
    return cells.load_reader(METRIC).read(ctx)


@pytest.mark.parametrize("name,by_hand", [
    ("ladder_dispatches.xspace.txt", 6.0),
    ("host_phases.xspace.txt", 1.0),
])
def test_reader_returns_the_hand_computed_value(name, by_hand, monkeypatch):
    assert read_from(fixture(name), monkeypatch) == pytest.approx(by_hand)


def test_only_the_worst_chips_lane_inside_the_window_counts():
    reader = cells.load_reader(METRIC)
    lines = host_phases.host_lines(
        xplane._profile(fixture("ladder_dispatches.xspace.txt")))
    us = 1e-6
    assert reader.dispatches(lines, 1000 * us, 3000 * us, 1) == 12
    assert reader.dispatches(lines, 1000 * us, 3000 * us, 0) == 3
    assert reader.dispatches(lines, 0.0, 3000 * us, 1) == 21  # with the x9
    assert reader.dispatches(lines, 1000 * us, 2000 * us, 1) == 7


def test_reader_leaves_the_metric_out_without_spans(monkeypatch):
    """A program without the tracer's bridge writes no ``ck/`` span: there is
    nothing to read, and the reader does not raise."""
    assert read_from(fixture("synthetic.xspace.txt"), monkeypatch) is None


def test_the_metric_is_listed_with_its_reader():
    (entry,) = [m for m in cells.manifest()["per_layer"]
                if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "fused dispatch",
        "moves": "items_per_s.balanced",
        "workloads": ["mandelbrot_balance_4chip"]}
    # by name: later PRs append behind it (it was the list's last at PR 25)
    assert cells.load_reader(METRIC) is not None
    assert METRIC in [m["name"] for m in cells.load_cell(
        "mandelbrot_balance_4chip").per_layer]
