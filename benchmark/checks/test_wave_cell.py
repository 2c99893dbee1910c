"""``wave_halo_4chip`` held to what the other cells are held to, at 128 x 128
cells on four lanes of the CPU container (``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8 python3 -m pytest
benchmark/checks/test_wave_cell.py -q``; tier-1's rig has the eight), and
its readers held to spans and a trace reduction made by hand.  Nothing here yields a device
number.

- the sound program reads ``correct`` true with exactly the cell's metrics;
- the control — the reference in bfloat16 in the program's place — fails on
  seeds 1, 2, 3, by the fresh call's limit;
- a window of idle calls reads ``correct`` false (the state's modes are
  short of the steps the calls count), and so does the program with its
  exchange between the lanes taken out, by the fresh call's limit AND by
  the energy's;
- the replay on tiles equals the replay of the whole membrane.

The hand-made reduction: two chips, a window of 2 s holding two calls of two
steps each; chip 1 worked longer (0.80 s of operations but its container).
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

CELL = "wave_halo_4chip"
NEW_METRICS = [
    "halo_idle_ms_per_call", "halo_bytes_per_step", "halo_host_hops",
    "wave_kernel_ms_per_iter", "wave_roofline", "device_idle_share.wave",
    "window_compiles.wave", "xla_launch_share.wave", "balance_moves.wave",
    "resync_idle_ms_per_call.wave", "dispatch_idle_ms_per_call.wave",
    "fence_idle_ms_per_call.wave", "unfused_computes_per_call.wave",
    "lane_imbalance.wave"]
COMPARED = ["u_fresh_rel_err", "energy_window_rel_err", "mode_window_rel_err",
            "cells_unwritten", "calls_not_tiling"]


def small_cell(side: int = 128) -> cells.Cell:
    cell = cells.load_cell(CELL)
    return cell._replace(
        cfg={**cell.cfg, "width": side, "height": side, "local_range": 64,
             "tile": 16, "tile_columns": 3, "edge_tiles": 8,
             "interior_tiles": 8},
        params={**cell.params, "n": side * side, "warmup_calls": 2,
                "warmup_quiet": {"calls": 2, "max_calls": 6}})


@pytest.fixture(scope="module")
def devices():
    from cekirdekler_tpu import hardware

    found = hardware.chip_devices()  # the host CPU under JAX_PLATFORMS=cpu
    if len(found) < 4:
        pytest.skip("four lanes need four host devices: set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8")
    return found


def test_sound_program_is_correct_with_exactly_the_cells_metrics(devices):
    compared = []
    result = run.run_cell(small_cell(), seed=2**31 + 5, seconds=0.3,
                          trace=False, devices=devices,
                          compared_out=compared)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"items_per_s.balanced", "setup_s"}
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]  # the numbers compared last
    assert [c.name for c in compared] == COMPARED
    # the drifting limits grow with the steps, the fresh call's does not
    by = {c.name: c for c in compared}
    lim = small_cell().cfg["limits"]
    assert by["energy_window_rel_err"].limit > lim[
        "energy_window_rel_err"]["at_zero"]
    assert by["u_fresh_rel_err"].limit == lim["u_fresh_rel_err"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bfloat16_control_fails(seed):
    cell = small_cell()
    data, values = cell.ref.inputs(cell.cfg, cell.params,
                                   np.random.default_rng(seed))
    observed = {"iterations": 13, "outputs": None, "ranges_log": [],
                "values": values,
                "fresh": {"iterations": 21, "outputs": None,
                          "values": values}}
    compared = cell.ref.compare(cell.cfg, cell.params, data, values,
                                observed, seed, precision="bfloat16")
    by = {c.name: c for c in compared}
    # by ONE of the cell's limits, not by each
    assert not by["u_fresh_rel_err"].ok, compared
    assert by["u_fresh_rel_err"].value > 1e-3
    assert all(c.ok for c in compared if c.name != "u_fresh_rel_err")


def test_window_of_idle_calls_is_not_correct(devices, monkeypatch):
    """The energy is the same after any number of steps; the modes are not:
    a window whose calls do nothing leaves the state short of the steps
    its calls count."""
    real_window = run.window

    def idle_window(ctx, seconds, compiles):
        call, ctx.call = ctx.call, lambda: None
        try:
            real_window(ctx, seconds, compiles)
        finally:
            ctx.call = call

    monkeypatch.setattr(run, "window", idle_window)
    compared = []
    result = run.run_cell(small_cell(), seed=11, seconds=0.05, trace=False,
                          devices=devices, compared_out=compared)
    assert result["correct"] is False and compared
    assert [c.name for c in compared if not c.ok] == ["mode_window_rel_err"]


def test_without_the_exchange_the_program_is_not_correct(devices, monkeypatch):
    """Every strip a lane takes from its neighbours thrown away: the lanes
    step on a halo that is a step (then two, then three) old."""
    from cekirdekler_tpu.core.cores import Cores

    real = Cores._stage_exchange

    def no_strips(self, *args, **kwargs):
        plans = real(self, *args, **kwargs)
        for plan in plans.values():
            plan.strips.clear()
        return plans

    monkeypatch.setattr(Cores, "_stage_exchange", no_strips)
    compared = []
    result = run.run_cell(small_cell(), seed=13, seconds=0.3, trace=False,
                          devices=devices, compared_out=compared)
    assert result["correct"] is False and result["failed"] == 0
    by = {c.name: c for c in compared}
    assert not by["u_fresh_rel_err"].ok and by["u_fresh_rel_err"].value > 0.1
    assert not by["energy_window_rel_err"].ok
    print("without the exchange:", compared)


def test_tile_replay_equals_the_whole_membranes():
    cell = small_cell(64)
    cfg = {**cell.cfg, "tile": 8}
    data, _ = cell.ref.inputs(cfg, {**cell.params, "n": 64 * 64},
                              np.random.default_rng(3))
    u0 = data["u0"]
    u1 = np.random.default_rng(4).standard_normal(64 * 64).astype(np.float32)
    u1.reshape(64, 64)[[0, -1], :] = 0.0
    u1.reshape(64, 64)[:, [0, -1]] = 0.0
    w0, w1 = cell.ref.whole_replay(cfg, u0, u1, 21)
    for top, left in [(0, 0), (56, 56), (0, 30), (28, 0), (25, 31), (56, 3)]:
        t0, t1 = cell.ref.tile_replay(cfg, u0, u1, 21, top, left, 8)
        cut = (slice(top, top + 8), slice(left, left + 8))
        np.testing.assert_array_equal(t0, w0.reshape(64, 64)[cut])
        np.testing.assert_array_equal(t1, w1.reshape(64, 64)[cut])


def test_tiles_straddle_every_lane_boundary():
    cell = small_cell()
    ranges = [4416, 3840, 4096, 4032]
    got = cell.ref.tiles(cell.cfg, [ranges], seed=5)
    assert len(got) == 8 + 3 * 3 * 3 + 8
    at = 0
    for share in ranges[:-1]:
        at += share
        row = at // 128
        assert sum(top <= row < top + 16 for top, _left in got) >= 3
    assert {(0, 0), (0, 112), (112, 0), (112, 112)} <= set(got)
    assert all(0 <= t <= 112 and 0 <= c <= 112 for t, c in got)


def test_energy_and_modes_by_hand():
    """The scheme keeps the energy to rounding and moves a mode as the
    closed form says, step by step, on a membrane small enough to replay."""
    cell = small_cell(32)
    cfg = {**cell.cfg, "modes": 3}
    data, _ = cell.ref.inputs(cfg, {**cell.params, "n": 32 * 32},
                              np.random.default_rng(8))
    picked = cell.ref.modes(cfg, 8)
    assert all(4 <= a <= 8 and 4 <= b <= 8 for a, b in picked)
    e0, _c, born = cell.ref.sums(cfg, data["u0"], data["u1"], picked)
    assert e0 > 0
    for steps in (1, 7, 50):
        a, b = cell.ref.whole_replay(cfg, data["u0"], data["u1"], steps)
        e, c0, c1 = cell.ref.sums(cfg, a, b, picked)
        assert abs(e - e0) / e0 < 1e-12
        want0, want1, swing = cell.ref.mode_after(cfg, picked, born, steps)
        np.testing.assert_allclose(c0, want0, atol=1e-10 * swing.max())
        np.testing.assert_allclose(c1, want1, atol=1e-10 * swing.max())
    # one step short shows
    _w0, short, swing = cell.ref.mode_after(cfg, picked, born, 49)
    assert np.abs(short - c1).max() > 0.05 * swing.max()


def test_kernel_cost_counts_the_least_bytes_by_hand():
    cell = small_cell()
    assert cell.ref.kernel_cost(cell.cfg, cell.params, 10) == {
        "ops": 90.0, "bytes": 280.0}


# -- the readers against spans and a reduction made by hand -----------------

OPS = {0: {("fusion.1", "fusion"): 0.30, ("while.1", "while"): 0.50},
       1: {("fusion.1", "fusion"): 0.50, ("dynamic-update-slice.2",
                                          "dynamic-update-slice"): 0.20,
           ("copy.3", "copy"): 0.10, ("while.1", "while"): 0.90}}


def by_hand() -> SimpleNamespace:
    cell = cells.load_cell(CELL)
    reduced = xplane.Reduced(
        t0=10.0, t1=12.0, busy_s={0: 0.5, 1: 1.5},
        op_seconds={k: dict(v) for k, v in OPS.items()},
        op_counts={k: {op: 4 for op in v} for k, v in OPS.items()},
        idle_by_span={0: {}, 1: {}}, calls=2)
    workers = [SimpleNamespace(index=k, device=SimpleNamespace(id=k))
               for k in (0, 1)]
    return SimpleNamespace(
        cell=cell, cfg=cell.cfg,
        params={**cell.params, "iterations_per_call": 2},
        n=int(cell.params["n"]), reduced=reduced, window_compiles=0,
        ranges_log=[[3000, 1000], [2000, 2000], [1000, 3000]],
        cr=SimpleNamespace(cores=SimpleNamespace(workers=workers)),
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def read(metric: str, ctx):
    return cells.load_reader(metric).read(ctx)


def test_step_time_is_the_busiest_chips_and_counts_copies():
    assert read("wave_kernel_ms_per_iter", by_hand()) == pytest.approx(200.0)


def test_roofline_is_the_lanes_share_over_bandwidth_over_step_time():
    ctx = by_hand()
    least_s = 28.0 * 2000 / 819e9  # chip 1 = lane 1: 2000 cells on average
    assert least_s > 9.0 * 2000 / 197e12  # bounded by memory
    assert read("wave_roofline", ctx) == pytest.approx(
        100.0 * least_s / 0.200)


def halo(start, lane, nbytes, tag="d2d", **stats) -> host_phases.HostSpan:
    return host_phases.HostSpan(
        "ck/halo", start, start + 0.001, 0,
        {"lane": lane, "bytes": nbytes, "tag": tag, **stats})


LINES = [[halo(9.5, 0, 512),                       # before the window
          halo(10.1, 0, 512, src="1"), halo(10.1, 1, 1024, src="0+2"),
          halo(10.6, 0, 512, src="1"), halo(10.6, 1, 4096, "d2d+host"),
          host_phases.HostSpan("ck/launch", 10.2, 10.3, 0, {"lane": 0}),
          halo(12.5, 1, 1024)]]                    # after it


def test_halo_readers_on_spans_by_hand():
    idle = cells.load_reader("halo_idle_ms_per_call")
    spans = idle.in_window(LINES, 10.0, 12.0)
    assert len(spans) == 4
    per_step = cells.load_reader("halo_bytes_per_step").bytes_per_step
    assert per_step(spans, 4) == pytest.approx((512 + 1024 + 512 + 4096) / 4)
    hops = cells.load_reader("halo_host_hops").host_hops
    assert hops(spans) == 1.0
    assert hops(spans[:3]) == 0.0
    # a program without the span (a parent commit) leaves nothing to read
    assert per_step([], 4) is None and hops([]) is None
    assert idle.in_window([[LINES[0][5]]], 10.0, 12.0) == []


def test_halo_idle_is_the_idle_under_the_span(monkeypatch):
    idle = cells.load_reader("halo_idle_ms_per_call")
    phases = host_phases.Phases(
        chip=1, lane=1, calls=2, idle_s=0.5,
        by_kind={"ck/halo": 0.030, "ck/launch": 0.2, "unnamed": 0.27},
        unnamed_by_bench={}, unfused_computes=4, queue_wait_s=0.0)
    monkeypatch.setattr(host_phases, "of", lambda ctx: phases)
    monkeypatch.setattr(idle, "halo_spans", lambda ctx: LINES[0][1:3])
    assert idle.read(by_hand()) == pytest.approx(15.0)
    # spans there, no idle under them: 0, not nothing
    monkeypatch.setattr(host_phases, "of", lambda ctx: phases._replace(
        by_kind={"ck/launch": 0.2}))
    assert idle.read(by_hand()) == 0.0
    monkeypatch.setattr(idle, "halo_spans", lambda ctx: [])
    assert idle.read(by_hand()) is None


def test_variants_are_read_by_their_quantities_readers():
    ctx = by_hand()
    assert read("window_compiles.wave", ctx) == 0.0
    assert read("device_idle_share.wave", ctx) == pytest.approx(75.0)
    assert read("balance_moves.wave", ctx) == 2.0
    assert read("lane_imbalance.wave", ctx) == pytest.approx(100.0 * 2 / 3)
    for metric in NEW_METRICS[7:]:
        assert cells.load_reader(metric) is not None


def test_readers_leave_the_metric_out_where_nothing_ran():
    ctx = by_hand()
    ctx.reduced = ctx.reduced._replace(op_seconds={0: {}, 1: {}})
    assert read("wave_kernel_ms_per_iter", ctx) is None
    assert read("wave_roofline", ctx) is None


def test_the_cell_and_its_entries_are_in_the_manifest_by_name():
    man = cells.manifest()
    row = next(w for w in man["workloads"] if w["name"] == CELL)
    assert row["chips"] == 4 and row["config"] == "wave_membrane"
    assert row["traffic"] == CELL
    conf = next(c for c in man["configs"] if c["name"] == "wave_membrane")
    assert conf["reduced"] == [] and len(conf["source"]) <= 200
    assert len(row["why"]) <= 200 and len(conf["why"]) <= 200
    listed = {m["name"]: m for m in man["per_layer"]}
    assert all(listed[m]["workloads"] == [CELL]
               and listed[m]["moves"] == "items_per_s.balanced"
               for m in NEW_METRICS)
    cell = cells.load_cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == [
        "items_per_s.balanced", "setup_s"]
    # by name and in their order: PR 35 appended the window's edge behind them
    assert [m["name"] for m in cell.per_layer
            if m["name"] in NEW_METRICS] == NEW_METRICS
    assert cell.cfg["source"] == conf["source"]
    assert cell.cfg["width"] * cell.cfg["height"] == cell.params["n"]
    # the kernel file is the example's source, letter for letter
    import re

    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "examples", "wave_equation.py")) as f:
        example = re.search(r'WAVE_SRC = """(.*?)"""', f.read(), re.S).group(1)
    assert cells.kernel_source(cell.cfg) == example
    # the other four-chip cell reports what it reported
    balanced = next(m for m in man["end_to_end"]
                    if m["name"] == "items_per_s.balanced")
    assert balanced["workloads"] == ["mandelbrot_balance_4chip", CELL]
    mandel = cells.load_cell("mandelbrot_balance_4chip")
    assert not {m["name"] for m in mandel.per_layer} & set(NEW_METRICS)
    assert [m["name"] for m in mandel.end_to_end] == [
        "items_per_s.balanced", "setup_s"]
