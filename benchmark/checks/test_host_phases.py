"""The reduction from the program's own spans to the worst chip's idle by
layer, and the eight readers on it, held to a trace made by hand (CPU
container, no chip: ``python3 -m pytest benchmark/checks/test_host_phases.py
-q``).

``fixtures/host_phases.xspace.txt`` is a text-format XSpace in microseconds:
two chips, the caller's thread and a driver thread per lane, two calls.  Chip
1 (lane 1) is busy 1100-1400, 1600-1900 and 2300-2800 in the window
1000-3000, so it is the worst chip, idle in four gaps of 100 + 200 + 400 + 200
= 900 us.  Worked out on paper, each stretch going to the SHORTEST ``ck/`` span
covering it on the caller's thread or on lane 1's:

    gap 1000-1100   enqueue 10, schedule 40, resync 5 + 5, download 30
                    (inside resync), launch 10 (lane 1's, 1060-1120, once
                    resync has ended)
    gap 1400-1600   enqueue 50 + 20 (lane 0's launch 1400-1450 is shorter than
                    the enqueue around it and must NOT be taken), no span 10
                    + 20, fused 20, drain 10, launch 70 (lane 1's, 1530-1640)
    gap 1900-2300   fence 50, no span 50 (between the barrier's fence and the
                    next call), enqueue 10 + 10 + 20, schedule 20, launch 10 +
                    50 + 10, compile 150 (inside lane 1's launch), no span 10
                    + 10
    gap 2800-3000   fence 190, no span 10
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

FIXTURE = os.path.join(HERE, "fixtures", "host_phases.xspace.txt")
US = 1e-6
#: metric -> value worked out above, per call (two calls)
BY_HAND = {
    "sched_idle_ms_per_call": 0.030,      # schedule 40 + 20
    "resync_idle_ms_per_call": 0.020,     # resync 10, download 30
    "dispatch_idle_ms_per_call": 0.150,   # enqueue 120, launch 150, fused 20, drain 10
    "fence_idle_ms_per_call": 0.120,      # 50 + 190
    "compile_idle_ms_per_call": 0.075,    # 150
    "unnamed_idle_share": 100.0 * 110 / 900,
    "unfused_computes_per_call": 1.0,     # one per-call enqueue in each call
    "driver_queue_wait_ms_per_call": 0.050,  # lane 1: 30 + 50 + 20 us
}


@pytest.fixture(scope="module")
def phases():
    reduced = xplane.reduce(xplane.load(FIXTURE))
    assert reduced.worst_chip == 1 and reduced.calls == 2
    return host_phases.reduce(xplane._profile(FIXTURE), reduced)


def test_idle_by_innermost_span_by_hand(phases):
    assert (phases.chip, phases.lane) == (1, 1)
    assert phases.idle_s == pytest.approx(900 * US)
    assert phases.by_kind == pytest.approx({
        "ck/enqueue": 120 * US, "ck/schedule": 60 * US, "ck/resync": 10 * US,
        "ck/download": 30 * US, "ck/launch": 150 * US, "ck/fused": 20 * US,
        "ck/drain": 10 * US, "ck/fence": 240 * US, "ck/compile": 150 * US,
        host_phases.UNNAMED: 110 * US})
    # what no span of the program names, by the benchmark's span around it
    assert phases.unnamed_by_bench == pytest.approx(
        {"bench/enqueue": 50 * US, "bench/barrier": 60 * US})


def test_parts_add_up_to_the_idle(phases):
    groups = list(host_phases.GROUPS) + [host_phases.DISPATCH]
    named = sum(phases.group_s(g) for g in groups)
    assert named + phases.by_kind[host_phases.UNNAMED] == pytest.approx(
        phases.idle_s)
    # and to what the benchmark's own reduction calls this chip's idle
    reduced = xplane.reduce(xplane.load(FIXTURE))
    assert phases.idle_s == pytest.approx(
        reduced.window_s - reduced.busy_s[phases.chip])


def test_another_lanes_launch_is_not_taken(phases):
    """Lane 0's launch (1400-1450, the shortest span over that stretch of
    chip 1's gap) explains nothing about chip 1: with it the launch total
    would read 200 us and the enqueue total 70."""
    assert phases.by_kind["ck/launch"] == pytest.approx(150 * US)
    assert phases.queue_wait_s == pytest.approx(100 * US)  # not lane 0's 500


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_reader_returns_the_hand_computed_value(metric, phases):
    ctx = SimpleNamespace(host_phases=phases)
    assert cells.load_reader(metric).read(ctx) == pytest.approx(
        BY_HAND[metric])


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_reader_leaves_the_metric_out_without_spans(metric):
    """A program without the tracer's bridge (a parent commit) writes no
    ``ck/`` span: there is nothing to read, and no reader raises."""
    fixture = os.path.join(HERE, "fixtures", "synthetic.xspace.txt")
    reduced = xplane.reduce(xplane.load(fixture))
    none = host_phases.reduce(xplane._profile(fixture), reduced)
    assert none is None
    assert cells.load_reader(metric).read(
        SimpleNamespace(host_phases=none)) is None


def test_every_new_metric_is_listed_with_its_reader():
    listed = {m["name"]: m for m in cells.manifest()["per_layer"]}
    for metric in BY_HAND:
        assert listed[metric]["workloads"] == ["mandelbrot_balance_4chip"]
        assert listed[metric]["moves"] == "items_per_s.balanced"
