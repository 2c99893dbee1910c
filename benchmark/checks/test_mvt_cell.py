"""``mvt_16k_window`` held to what the other cells are held to, at n = 256 on
the CPU container (``JAX_PLATFORMS=cpu python3 -m pytest
benchmark/checks/test_mvt_cell.py -q``), and its readers held to a trace
reduction made by hand.  Nothing here yields a device number.

- the sound program reads ``correct`` true with exactly the cell's metrics;
- the control — the reference in bfloat16 in the program's place — fails on
  seeds 1, 2, 3;
- the kernel with its stride taken out (``a[i]`` where ``a[i * n + j]`` is
  due) reads ``correct`` false, and so does a window of idle calls.

The hand-made reduction: one chip, a window of 2 s holding two calls of two
computes each, whose operations took

    fusion.7     0.20 s   a gather fusion (its HLO text says so)
    scatter.2    0.10 s   a bare scatter
    add_fusion   0.40 s   a loop fusion
    copy.3       0.10 s   a copy: counted here (a transposition is the
                          kernel's work wherever the compiler puts it)
    while.1      0.90 s   a container: its body is what is listed above

so the compute's time is 0.80 s = 200 ms a compute.
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

CELL = "mvt_16k_window"
NEW_METRICS = ["mvt_kernel_ms_per_iter", "mvt_roofline", "mvt_gather_share",
               "mvt_gathered_accesses", "xla_launch_share.mvt",
               "window_compiles.mvt", "device_idle_share.mvt"]
ROW_WALK = "x1[i] += a[i * n + j] * y1[j];"


def small_cell(n: int = 256) -> cells.Cell:
    cell = cells.load_cell(CELL)
    return cell._replace(cfg={**cell.cfg, "n_matrix": n, "local_range": 64},
                         params={**cell.params, "n": n})


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
    # not items_per_s: the same quantity as call_p50_ms here (one compute a
    # call, one caller), and the whole-process pauses of the chip's host
    # move a 30 s rate by more than half its bound (PERF.md s.2, PR 30)
    assert set(result["metrics"]) == {"call_p50_ms", "setup_s"}
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]  # the numbers compared last
    # every element of both vectors, twice: the window's state, the fresh call's
    assert [c.name for c in compared] == ["x_window_rel_err",
                                          "x_fresh_rel_err"]
    # the window's limit grows with the computes, the fresh call's does not
    assert compared[0].limit > compared[1].limit


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bfloat16_control_fails(seed):
    cell = small_cell()
    data, values = cell.ref.inputs(cell.cfg, cell.params,
                                   np.random.default_rng(seed))
    observed = {"iterations": 13, "outputs": None, "ranges_log": [],
                "values": values,
                "fresh": {"iterations": 3, "outputs": None,
                          "values": values}}
    compared = cell.ref.compare(cell.cfg, cell.params, data, values,
                                observed, seed, precision="bfloat16")
    assert compared and not compared[1].ok, compared


def test_kernel_without_its_stride_is_not_correct(devices, monkeypatch):
    source = cells.kernel_source(cells.load_cell(CELL).cfg)
    assert ROW_WALK in source
    monkeypatch.setattr(
        cells, "kernel_source",
        lambda cfg: source.replace(ROW_WALK, "x1[i] += a[i] * y1[j];"))
    result = run.run_cell(small_cell(), seed=7, seconds=0.3, trace=False,
                          devices=devices)
    assert result["correct"] is False


def test_window_of_idle_calls_is_not_correct(devices, monkeypatch):
    """The state accumulates from zero through warm-up and window; a window
    whose calls do nothing leaves fewer steps in it than its computes
    count."""
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
    # the fresh call was sound: it is the window's steps that are missing
    assert [c.ok for c in compared] == [False, True]


def test_reference_is_the_two_products_by_hand():
    """n = 4, by hand: A = [[1, 2, 3, 4], [5, 6, 7, 8], ...]."""
    cell = small_cell(4)
    a = np.arange(1, 17, dtype=np.float32)
    arrays = {"a": a, "y1": np.array([1, 0, 2, 0], np.float32),
              "y2": np.array([0, 1, 0, 1], np.float32)}
    s1, s2 = cell.ref.step(cell.cfg, arrays)
    assert s1.tolist() == [7.0, 19.0, 31.0, 43.0]    # A y1: a[i,0] + 2 a[i,2]
    assert s2.tolist() == [18.0, 20.0, 22.0, 24.0]   # A^T y2: rows 1 and 3
    c1, c2 = cell.ref.step(cell.cfg, arrays, "bfloat16")
    assert c1.tolist() == s1.tolist() and c2.tolist() == s2.tolist()


def test_kernel_cost_counts_the_least_bytes_by_hand():
    """n = 4: 16 elements, a multiply and an add each, in each of the two
    kernels; ``a`` once a kernel and three vector passes a kernel."""
    cell = small_cell(4)
    cost = cell.ref.kernel_cost(cell.cfg, cell.params, 4)
    assert cost == {"ops": 64.0, "bytes": 2 * 64.0 + 6 * 16.0}
    half = cell.ref.kernel_cost(cell.cfg, cell.params, 2)
    assert half == {"ops": 32.0, "bytes": 112.0}


# -- the readers against a reduction made by hand ---------------------------

OPS = {("fusion.7", "fusion"): 0.20, ("scatter.2", "scatter"): 0.10,
       ("add_fusion", "fusion"): 0.40, ("copy.3", "copy"): 0.10,
       ("while.1", "while"): 0.90}


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


def test_compute_time_counts_copies_and_leaves_out_containers():
    assert read("mvt_kernel_ms_per_iter", by_hand()) == pytest.approx(200.0)


def test_roofline_is_least_bytes_over_bandwidth_over_compute_time():
    ctx = by_hand()
    n = 16384
    least_s = (8.0 * n * n + 24.0 * n) / 819e9
    assert least_s > 4.0 * n * n / 197e12  # bounded by memory
    assert read("mvt_roofline", ctx) == pytest.approx(100.0 * least_s / 0.200)


GATHER_FUSION = (
    "%fusion.7 = f32[16384]{0:T(1024)} fusion(f32[268435456]{0:T(1024)} "
    "%get-tuple-element.11, s32[16384]{0:T(1024)S(1)} "
    "%get-tuple-element.9), kind=kCustom, calls=%fused_computation.7")
LOOP_FUSION = (
    "%multiply_add_fusion = f32[16384]{0:T(1024)} fusion("
    "f32[268435456]{0:T(1024)} %p), kind=kLoop, calls=%fused_computation.5")
BARE_SCATTER = ("%scatter.2 = f32[16384]{0} scatter(f32[16384]{0} %p, "
                "s32[16384,1]{1,0} %i, f32[16384]{0} %u), to_apply=%assign")
COPY = "%copy.3 = f32[128,16384]{1,0:T(8,128)} copy(f32[128,16384]{0,1} %g)"


def test_gather_share_counts_gathers_scatters_and_custom_fusions(monkeypatch):
    """Inside the 10-12 s window: the custom fusion 10.0-10.2, the scatter
    10.2-10.3 and another 11.9-12.3 of which 0.1 s lies inside: 0.40 of
    the compute's 0.80 s."""
    reader = cells.load_reader("mvt_gather_share")
    assert reader.is_per_lane(GATHER_FUSION) and reader.is_per_lane(BARE_SCATTER)
    assert not reader.is_per_lane(LOOP_FUSION) and not reader.is_per_lane(COPY)
    events = [(GATHER_FUSION, 9.0, 9.5),       # before the window
              (GATHER_FUSION, 10.0, 10.2), (BARE_SCATTER, 10.2, 10.3),
              (LOOP_FUSION, 10.3, 10.7), (COPY, 10.7, 10.8),
              (BARE_SCATTER, 11.9, 12.3)]      # cut by the window's end
    assert reader.per_lane_seconds(events, 10.0, 12.0) == pytest.approx(0.4)
    monkeypatch.setattr(reader, "chip_events", lambda profile, chip: events)
    monkeypatch.setattr(xplane, "find_xplane", lambda _dir: os.path.join(
        HERE, "fixtures", "synthetic.xspace.txt"))
    assert reader.read(by_hand()) == pytest.approx(100.0 * 0.4 / 0.8)


def span(name, start, lane, **stats) -> host_phases.HostSpan:
    return host_phases.HostSpan(name, start, start + 0.01, 1,
                                {"lane": lane, **stats})


SLICED = "slice:1;strided:1;uniform:2;gather:0;scatter:0;carried:2"
GATHERED = "slice:0;strided:0;uniform:2;gather:2;scatter:2;carried:0"


def test_gathered_accesses_reads_the_first_calls_launch_spans():
    gathered = cells.load_reader("mvt_gathered_accesses").gathered
    lines = [[span("ck/launch", 9.0, 0, win=6, access=GATHERED),   # before
              span("ck/launch", 10.1, 0, win=7, access=SLICED),
              span("ck/fused", 10.2, 0, win=7, access=SLICED),
              span("ck/launch", 10.3, 1, win=7, access=GATHERED)],  # lane 1
             [span("ck/launch", 11.0, 0, win=8, access=GATHERED)]]  # later
    assert gathered(lines, 10.0, 12.0, 0) == 0
    assert gathered(lines, 10.0, 12.0, 1) == 4
    assert gathered(lines, 10.9, 12.0, 0) == 4
    # a program whose spans do not count their accesses leaves nothing
    silent = [[span("ck/launch", 10.1, 0, win=7, lowering="xla")]]
    assert gathered(silent, 10.0, 12.0, 0) is None
    assert gathered(lines, 10.0, 12.0, 2) is None


def test_variants_are_read_by_their_quantities_readers():
    ctx = by_hand()
    assert read("window_compiles.mvt", ctx) == 0.0
    assert read("device_idle_share.mvt", ctx) == pytest.approx(25.0)
    assert cells.load_reader("xla_launch_share.mvt") is not None


def test_readers_leave_the_metric_out_where_nothing_ran():
    ctx = by_hand()
    ctx.reduced = ctx.reduced._replace(op_seconds={0: {}})
    for metric in NEW_METRICS[:3]:
        assert read(metric, ctx) is None  # before any trace is looked for


def test_the_cell_and_its_metrics_are_in_the_manifest():
    man = cells.manifest()
    row = next(w for w in man["workloads"] if w["name"] == CELL)
    assert row["chips"] == 1 and row["config"] == "polybench_mvt"
    conf = next(c for c in man["configs"] if c["name"] == "polybench_mvt")
    assert conf["reduced"] == [] and len(conf["source"]) <= 200
    assert len(row["why"]) <= 200 and len(conf["why"]) <= 200
    listed = {m["name"]: m for m in man["per_layer"]}
    assert all(listed[m]["workloads"] == [CELL]
               and listed[m]["moves"] == "call_p50_ms" for m in NEW_METRICS)
    cell = cells.load_cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == ["call_p50_ms", "setup_s"]
    # by name and in their order: PR 35 appended the window's edge behind them
    assert [m["name"] for m in cell.per_layer
            if m["name"] in NEW_METRICS] == NEW_METRICS
    assert cell.cfg["source"] == conf["source"]
    # the accepted cells report what they reported, by name, whatever a later
    # PR appends
    spmv = cells.load_cell("spmv_hpcg256_window")
    assert [m["name"] for m in spmv.per_layer] == [
        "spmv_kernel_ms_per_iter", "spmv_roofline", "spmv_gather_share",
        "xla_launch_share", "window_compiles.spmv", "device_idle_share.spmv"]
    assert [m["name"] for m in spmv.end_to_end] == [
        "items_per_s", "call_p50_ms", "setup_s"]
    assert all(listed[m["name"]]["workloads"] == ["spmv_hpcg256_window"]
               and listed[m["name"]]["moves"] == "items_per_s"
               for m in spmv.per_layer)
    row = next(w for w in man["workloads"] if w["name"] == "spmv_hpcg256_window")
    assert row["chips"] == 1 and row["config"] == "hpcg_spmv"
    for name in ("nbody_8k_window", "nbody_32k_window"):
        assert [m["name"] for m in cells.load_cell(name).per_layer][:4] == [
            "window_compiles", "device_idle_share", "kernel_ms_per_iter",
            "nbody_roofline"]  # later PRs appended variants behind them
