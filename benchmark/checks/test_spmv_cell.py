"""``spmv_hpcg256_window`` held to what the other cells are held to, at 16^3 on
the CPU container (``JAX_PLATFORMS=cpu python3 -m pytest
benchmark/checks/test_spmv_cell.py -q``), and its readers held to a trace
reduction made by hand.  Nothing here yields a device number.

- the sound program reads ``correct`` true with exactly the cell's metrics;
- the control — the reference from the grid in bfloat16 in the program's
  place — fails on seeds 1, 2, 3;
- the kernel with the gather taken out (``x[i]`` where ``x[col[j]]`` is due)
  reads ``correct`` false, and so does a window of idle calls.

The hand-made reduction: one chip, a window of 2 s holding two calls of two
products each, whose operations took

    fusion.1   0.30 s   a gather fusion (the name says nothing; its HLO
                        text does, which the gather reader goes through)
    gather.2   0.10 s   a bare gather
    add_fusion 0.20 s   a loop fusion
    copy.3     0.05 s   a copy: not the kernel's
    while.1    0.70 s   a container: its body is what is listed above

so the kernel's time is 0.60 s = 150 ms a product.
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

CELL = "spmv_hpcg256_window"
NEW_METRICS = ["spmv_kernel_ms_per_iter", "spmv_roofline",
               "spmv_gather_share", "xla_launch_share",
               "window_compiles.spmv", "device_idle_share.spmv"]
GATHER = "s += val[j] * x[col[j]];"


def small_cell() -> cells.Cell:
    cell = cells.load_cell(CELL)
    return cell._replace(
        cfg={**cell.cfg, "nx": 16, "ny": 16, "nz": 16},
        params={**cell.params, "n": 4096, "iterations_per_call": 3})


@pytest.fixture(scope="module")
def devices():
    from cekirdekler_tpu import hardware

    return hardware.chip_devices()  # the host CPU under JAX_PLATFORMS=cpu


def test_sound_program_is_correct_with_exactly_the_cells_metrics(devices):
    compared = []
    result = run.run_cell(small_cell(), seed=2**31 + 5, seconds=0.3,
                          trace=False, devices=devices,
                          compared_out=compared)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"items_per_s", "call_p50_ms",
                                      "setup_s"}
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]  # the numbers compared last
    # every row, twice: the window's last call and the fresh call
    assert [c.name for c in compared] == ["y_window_rel_err",
                                          "y_fresh_rel_err"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bfloat16_control_fails(seed):
    cell = small_cell()
    data, values = cell.ref.inputs(cell.cfg, cell.params,
                                   np.random.default_rng(seed))
    plan = cell.ref.call_values(cell.cfg, cell.params, values)
    observed = {"iterations": 121, "outputs": None, "ranges_log": [],
                "values": plan["cycle"][-1],
                "fresh": {"iterations": 4, "outputs": None,
                          "values": plan["apart"]}}
    compared = cell.ref.compare(cell.cfg, cell.params, data, values,
                                observed, seed, precision="bfloat16")
    assert compared and not any(c.ok for c in compared), compared


def test_kernel_without_its_gather_is_not_correct(devices, monkeypatch):
    source = cells.kernel_source(cells.load_cell(CELL).cfg)
    assert GATHER in source
    monkeypatch.setattr(
        cells, "kernel_source",
        lambda cfg: source.replace(GATHER, "s += val[j] * x[i];"))
    result = run.run_cell(small_cell(), seed=7, seconds=0.3, trace=False,
                          devices=devices)
    assert result["correct"] is False


def test_window_of_idle_calls_is_not_correct(devices, monkeypatch):
    """Warm-up leaves a sound product in ``y``, with the alpha set apart; a
    window whose calls then do nothing must not be taken for one that
    worked."""
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
    assert result["correct"] is False
    assert result["attempted"] >= 1 and compared
    # the fresh call was sound: it is the window's product that is missing
    assert [c.ok for c in compared] == [False, True]


def grid_sizes(cfg) -> tuple[int, int]:
    """(rows, stored nonzeros) of the configuration's grid, by hand."""
    nx, ny, nz = cfg["nx"], cfg["ny"], cfg["nz"]
    return nx * ny * nz, (3 * nx - 2) * (3 * ny - 2) * (3 * nz - 2)


def test_kernel_cost_counts_the_least_bytes():
    cell = cells.load_cell(CELL)
    n, nnz = grid_sizes(cell.cfg)
    assert n == int(cell.params["n"])  # one work-item a row
    cost = cell.ref.kernel_cost(cell.cfg, cell.params, n)
    assert cost == {"ops": 2.0 * nnz + n,
                    "bytes": 8.0 * nnz + 4.0 * (n + 1) + 8.0 * n}
    half = cell.ref.kernel_cost(cell.cfg, cell.params, n // 2)
    assert half["bytes"] == cost["bytes"] / 2


# -- the readers against a reduction made by hand ---------------------------

OPS = {("fusion.1", "fusion"): 0.30, ("gather.2", "gather"): 0.10,
       ("add_fusion", "fusion"): 0.20, ("copy.3", "copy"): 0.05,
       ("while.1", "while"): 0.70}


def by_hand() -> SimpleNamespace:
    cell = cells.load_cell(CELL)
    reduced = xplane.Reduced(
        t0=10.0, t1=12.0, busy_s={0: 1.5}, op_seconds={0: dict(OPS)},
        op_counts={0: {k: 4 for k in OPS}}, idle_by_span={0: {}}, calls=2)
    return SimpleNamespace(
        cell=cell, cfg=cell.cfg, params={**cell.params,
                                         "iterations_per_call": 2},
        n=int(cell.params["n"]), reduced=reduced, window_compiles=0,
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def read(metric: str, ctx):
    return cells.load_reader(metric).read(ctx)


def test_kernel_time_leaves_out_copies_and_containers():
    assert read("spmv_kernel_ms_per_iter", by_hand()) == pytest.approx(150.0)


def test_roofline_is_least_bytes_over_bandwidth_over_kernel_time():
    ctx = by_hand()
    n, nnz = grid_sizes(ctx.cfg)
    least_s = (8.0 * nnz + 4.0 * (n + 1) + 8.0 * n) / 819e9
    assert least_s > (2.0 * nnz + n) / 197e12  # bounded by memory
    assert read("spmv_roofline", ctx) == pytest.approx(
        100.0 * least_s / 0.150)


GATHER_FUSION = (
    "%fusion.1 = s32[16777216]{0:T(1024)} fusion(s32[449455096]{0:T(1024)} "
    "%get-tuple-element.119, s32[16777216]{0:T(1024)S(1)} "
    "%get-tuple-element.91), kind=kCustom, calls=%fused_computation.1")
LOOP_FUSION = (
    "%broadcast_clamp_fusion = s32[16777216]{0:T(1024)} fusion("
    "s32[16777216]{0:T(1024)} %fusion.1), kind=kLoop, "
    "calls=%fused_computation.5")
BARE_GATHER = ("%gather.2 = f32[64]{0} gather(f32[4096]{0} %p, s32[64,1]{1,0} "
               "%i), offset_dims={}, slice_sizes={1}")
COPY = "%copy.7 = s32[16777216]{0:T(1024)} copy(s32[16777216]{0:T(1024)} %g)"


def test_gather_share_counts_gathers_and_the_fusions_around_them(monkeypatch):
    """The event texts are this cell's own, off its first trace on the chip.
    Inside the 10-12 s window: the custom fusion 10.0-10.3, the bare gather
    10.3-10.4 and another 11.9-12.2 of which 0.1 s lies inside: 0.50 of the
    kernel's 0.60 s."""
    reader = cells.load_reader("spmv_gather_share")
    assert reader.is_gather(GATHER_FUSION) and reader.is_gather(BARE_GATHER)
    assert not reader.is_gather(LOOP_FUSION) and not reader.is_gather(COPY)
    events = [(GATHER_FUSION, 9.0, 9.5),       # before the window
              (GATHER_FUSION, 10.0, 10.3), (BARE_GATHER, 10.3, 10.4),
              (LOOP_FUSION, 10.4, 10.6), (COPY, 10.6, 10.65),
              (BARE_GATHER, 11.9, 12.2)]       # cut by the window's end
    assert reader.gather_seconds(events, 10.0, 12.0) == pytest.approx(0.5)
    monkeypatch.setattr(reader, "chip_events", lambda profile, chip: events)
    monkeypatch.setattr(xplane, "find_xplane", lambda _dir: os.path.join(
        HERE, "fixtures", "synthetic.xspace.txt"))
    assert reader.read(by_hand()) == pytest.approx(100.0 * 0.5 / 0.6)


def test_variants_are_read_by_their_quantities_readers():
    ctx = by_hand()
    assert read("window_compiles.spmv", ctx) == 0.0
    assert read("device_idle_share.spmv", ctx) == pytest.approx(25.0)


def span(name, start, lane, **stats) -> host_phases.HostSpan:
    return host_phases.HostSpan(name, start, start + 0.01, 1,
                                {"lane": lane, **stats})


def test_xla_launch_share_over_the_lanes_launches_in_the_window():
    share = cells.load_reader("xla_launch_share").share
    lines = [[span("ck/launch", 9.0, 0, lowering="xla"),     # before it
              span("ck/launch", 10.1, 0, lowering="xla"),
              span("ck/launch", 10.5, 0, lowering="pallas"),
              span("ck/launch", 10.7, 1, lowering="pallas"),  # another lane
              span("ck/fused", 10.8, 0, lowering="xla")],     # not a launch
             [span("ck/launch", 11.0, 0, lowering="xla"),
              span("ck/launch", 11.5, 0, lowering="pallas+xla")]]
    assert share(lines, 10.0, 12.0, 0) == pytest.approx(50.0)
    assert share(lines, 10.0, 12.0, 1) == 0.0
    # a program that does not name its lowering leaves nothing to read
    silent = [[span("ck/launch", 10.1, 0), span("ck/launch", 10.5, 0)]]
    assert share(silent, 10.0, 12.0, 0) is None
    assert share(lines, 10.0, 12.0, 2) is None


def test_readers_leave_the_metric_out_where_nothing_ran():
    ctx = by_hand()
    ctx.reduced = ctx.reduced._replace(op_seconds={0: {}})
    for metric in NEW_METRICS[:3]:
        assert read(metric, ctx) is None  # before any trace is looked for


def test_the_cell_and_its_metrics_are_appended_to_the_manifest():
    # found BY NAME: what stood last when this cell was appended (PR 26) has
    # later cells' entries behind it, in the order it was appended in
    man = cells.manifest()
    row = next(w for w in man["workloads"] if w["name"] == CELL)
    assert row["chips"] == 1 and row["config"] == "hpcg_spmv"
    assert "hpcg_spmv" in [c["name"] for c in man["configs"]]
    mine = [m for m in man["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in mine] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "items_per_s"
               for m in mine)
    cell = cells.load_cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == [
        "items_per_s", "call_p50_ms", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == NEW_METRICS
    # the accepted cells report what they reported
    for name in ("nbody_8k_window", "nbody_32k_window"):
        assert [m["name"] for m in cells.load_cell(name).per_layer][:4] == [
            "window_compiles", "device_idle_share", "kernel_ms_per_iter",
            "nbody_roofline"]  # later PRs appended variants behind them
