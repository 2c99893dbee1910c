"""The n-body cells' split of the chip's idle by the program's spans (PR 34:
``dispatch_idle_ms_per_call.nbody``, ``fence_idle_ms_per_call.nbody``,
``unfused_computes_per_call.nbody``, ``unnamed_idle_share.nbody``), held by
NAME, whatever a later PR appends; and the harness's own windows at CPU size
(``JAX_PLATFORMS=cpu python3 -m pytest benchmark/checks/test_nbody_starts.py
-q``): a window that repeats the last one starts on the fused ladder, the
fresh call's does not, and the run reads ``correct``.  Nothing here yields a
device number.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]

import cells  # noqa: E402
import run  # noqa: E402

NBODY = ["nbody_8k_window", "nbody_32k_window"]
ACCEPTED = ["window_compiles", "device_idle_share", "kernel_ms_per_iter",
            "nbody_roofline"]
VARIANTS = ["dispatch_idle_ms_per_call.nbody", "fence_idle_ms_per_call.nbody",
            "unfused_computes_per_call.nbody", "unnamed_idle_share.nbody"]


@pytest.mark.parametrize("metric", VARIANTS)
def test_a_variant_is_listed_as_its_quantity_is(metric):
    listed = {m["name"]: m for m in cells.manifest()["per_layer"]}
    entry, quantity = listed[metric], listed[cells.quantity(metric)]
    assert entry["workloads"] == NBODY and entry["moves"] == "items_per_s"
    assert {k: entry[k] for k in ("unit", "better", "source", "layer")} == {
        k: quantity[k] for k in ("unit", "better", "source", "layer")}
    # no file of its own: the quantity's reader reads it
    assert not os.path.exists(os.path.join(
        os.path.dirname(HERE), "layer_metrics", metric + ".py"))
    assert cells.load_reader(metric).__file__.endswith(
        cells.quantity(metric) + ".py")


@pytest.mark.parametrize("name", NBODY)
def test_the_nbody_cells_report_what_they_reported_and_the_split(name):
    cell = cells.load_cell(name)
    names = [m["name"] for m in cell.per_layer]
    assert names[:len(ACCEPTED)] == ACCEPTED
    assert [n for n in names if n.endswith(".nbody")] == VARIANTS
    assert "items_per_s" in [m["name"] for m in cell.end_to_end]


def test_no_other_cell_reports_the_split():
    man = cells.manifest()
    for row in man["workloads"]:
        if row["name"] not in NBODY:
            assert not [m["name"] for m in cells.load_cell(row["name"]).per_layer
                        if m["name"].endswith(".nbody")]


def test_the_harness_windows_start_on_the_ladder_and_stay_correct(monkeypatch):
    """At CPU size: every window of the timed loop after the first starts
    on the ladder (its first compute is deferred), the fresh call's window,
    whose state was uploaded anew, starts per call, and ``correct`` holds:
    ``vel_window_rel_err`` counts the window's iterations, so a window that
    started on the ladder and was flushed brought every one back."""
    from cekirdekler_tpu import hardware

    cell = cells.load_cell("nbody_8k_window")
    cell = cell._replace(params={**cell.params, "n": 512,
                                 "iterations_per_call": 5})
    seen = {}
    read_back = run.read_back

    def spy(ctx):
        seen["window"] = dict(ctx.cr.fused_stats["window_starts"])
        out = read_back(ctx)
        seen["all"] = dict(ctx.cr.fused_stats["window_starts"])
        seen["calls"] = len(ctx.walls)
        return out

    monkeypatch.setattr(run, "read_back", spy)
    result = run.run_cell(cell, seed=2147483999, seconds=0.2, trace=False,
                          devices=hardware.chip_devices())
    assert result["correct"] is True and result["failed"] == 0
    starts = dict(seen["window"])
    # warm-up's first window is the process's first sighting; every later
    # window, warm-up's and the timed loop's, repeats the one before it
    assert starts.pop("first-sighting") == 1
    assert set(starts) == {"ladder"}, starts
    assert starts["ladder"] >= seen["calls"] >= 1
    fresh = {k: v - seen["window"].get(k, 0) for k, v in seen["all"].items()
             if v != seen["window"].get(k, 0)}
    assert fresh == {"non-resident": 1}, fresh
