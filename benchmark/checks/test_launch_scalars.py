"""``launch_ms_per_call`` and ``loose_scalars_per_call`` (ISSUE 39) held to a
trace made by hand, and found in the manifest BY NAME (a later PR appends
behind them).  Nothing here yields a device number
(``JAX_PLATFORMS=cpu python3 -m pytest benchmark/checks/test_launch_scalars.py
-q``).

The trace by hand: lane 0, window 10-12 s.  Call ``win`` 7 is four launches
of 0.5, 0.4, 0.4 and 0.3 ms, the streamed path's chunks on the lane's stream
driver thread, each ``scalars=packed:7;loose:0``; call 8 is one launch of
2.4 ms on the phase thread that handed one value over as a ``jax.Array``, two
loose and five words packed.  Call 6 began before the window, call 9 runs past
its end (its first launch lies inside: the call is left out whole), lane 1's
launch is another chip's, and an instant (a mark) is no launch.
"""

import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]

import cells  # noqa: E402
import host_phases  # noqa: E402
import xplane  # noqa: E402

CELL = "mandelbrot_percall_1chip"
METRICS = {"launch_ms_per_call": "ms", "loose_scalars_per_call": "count"}
LAUNCH = "ck/launch"


def launch(start, ms, line, win, lane=0, scalars=None) -> host_phases.HostSpan:
    stats = {"lane": lane, "win": win, "tag": "mandelbrot x1",
             "lowering": "pallas"}
    if scalars is not None:
        stats["scalars"] = scalars
    return host_phases.HostSpan(LAUNCH, start, start + 1e-3 * ms, line, stats)


def by_hand(field: bool = True) -> list:
    packed = "packed:7;loose:0" if field else None
    driver = [  # the lane's stream driver thread
        launch(9.9990, 0.5, 1, 6, scalars=packed),   # began before the window
        launch(10.0010, 0.5, 1, 6, scalars=packed),
        launch(10.1000, 0.5, 1, 7, scalars=packed),
        launch(10.1030, 0.4, 1, 7, scalars=packed),
        launch(10.1060, 0.4, 1, 7, scalars=packed),
        launch(10.1090, 0.3, 1, 7, scalars=packed),
        launch(11.9990, 0.5, 1, 9, scalars=packed),
        launch(12.0010, 0.5, 1, 9, scalars=packed),  # past the end
    ]
    phase = [  # the lane's phase thread
        launch(11.0000, 2.4, 2, 8,
               scalars="packed:5;loose:2" if field else None),
        host_phases.HostSpan(LAUNCH, 11.5, 11.5, 2,
                             {"lane": 0, "win": 8, "tag": "part:mark"}),
        host_phases.HostSpan("ck/download", 11.1, 11.2, 2,
                             {"lane": 0, "win": 8, "tag": "out"}),
    ]
    other = [launch(10.5000, 9.0, 3, 7, lane=1, scalars="packed:0;loose:9")]
    return [[], driver, phase, other]


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


def test_launches_are_summed_a_call_over_the_calls_inside_the_window():
    reader = cells.load_reader("launch_ms_per_call")
    r = reader.reduce(by_hand(), 10.0, 12.0, lane=0)
    assert (r.calls, r.spans, r.packed, r.loose) == (2, 5, 33, 2)
    assert r.launch_s == pytest.approx(1e-3 * (0.5 + 0.4 + 0.4 + 0.3 + 2.4))
    # the other chip's lane reads its own launch, a lane without any nothing
    other = reader.reduce(by_hand(), 10.0, 12.0, lane=1)
    assert (other.calls, other.spans, other.loose) == (1, 1, 9)
    assert other.launch_s == pytest.approx(9e-3)
    assert reader.reduce(by_hand(), 10.0, 12.0, lane=2) is None


def test_both_readers_read_the_trace_by_hand(monkeypatch):
    ctx = ctx_by_hand(monkeypatch, by_hand())
    assert cells.load_reader("launch_ms_per_call").read(ctx) == pytest.approx(
        (1.6 + 2.4) / 2)
    assert cells.load_reader("loose_scalars_per_call").read(ctx) == 1.0


def test_a_program_without_the_field_reads_the_launches_and_no_scalars(
        monkeypatch):
    """A parent commit: the host's time in its launches reads as on the
    change, so the ledger gets both sides; the count is left out."""
    ctx = ctx_by_hand(monkeypatch, by_hand(field=False))
    assert cells.load_reader("launch_ms_per_call").read(ctx) == pytest.approx(
        (1.6 + 2.4) / 2)
    assert cells.load_reader("loose_scalars_per_call").read(ctx) is None
    # and a window without launches of the lane leaves both out
    ctx = ctx_by_hand(monkeypatch, [[], [], [], []])
    assert cells.load_reader("launch_ms_per_call").read(ctx) is None
    assert cells.load_reader("loose_scalars_per_call").read(ctx) is None


def test_every_packed_dispatch_reads_no_loose_scalar(monkeypatch):
    lines = by_hand()
    lines[2] = []  # the call that handed a value over as an array is gone
    ctx = ctx_by_hand(monkeypatch, lines)
    r = cells.load_reader("launch_ms_per_call").of(ctx)
    assert (r.calls, r.spans, r.packed, r.loose) == (1, 4, 28, 0)
    assert cells.load_reader("loose_scalars_per_call").read(ctx) == 0.0


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_entry_and_its_reader_are_in_the_manifest_by_name(name):
    man = cells.manifest()
    rows = [m for m in man["per_layer"] if m["name"] == name]
    assert len(rows) == 1
    assert rows[0] == {
        "name": name, "unit": METRICS[name], "better": "lower",
        "source": "program_span", "layer": "fused dispatch",
        "moves": "call_p50_ms.percall", "workloads": [CELL]}
    # the layer is one the manifest had, letter for letter
    assert sum(m["layer"] == "fused dispatch" for m in man["per_layer"]) > 2
    # a file of its own, and the cell reports it and what it moves
    assert os.path.exists(os.path.join(
        os.path.dirname(HERE), "layer_metrics", name + ".py"))
    assert callable(cells.load_reader(name).read)
    cell = cells.load_cell(CELL)
    assert name in {m["name"] for m in cell.per_layer}
    assert "call_p50_ms.percall" in {m["name"] for m in cell.end_to_end}
