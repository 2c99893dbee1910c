"""chip_smoke.py's stage functions on the CPU rig: toy sizes, kernels
interpreted (by the rule the chip run takes too: the lowering follows the
lane), results checked by the stages' own comparisons.  The real run is
`python chip_smoke.py` on the chip; this keeps the script itself from
rotting between chip runs, and pins that it refuses to run without one."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from cekirdekler_tpu.hardware import platforms  # noqa: E402

TOY = dict(
    chip_smoke.FULL,
    mandel_wh=64, mandel_max_iter=32, local_range=128,
    mandel_per_call=3, mandel_window=6, mandel_marker_window=4,
    nbody_n=256, nbody_iters=6, nbody_window=3,
    halo_wh=64, halo_window=6, bfs_nodes=1000, reduce_elements=1 << 17,
    md_side=8, md_neighbours=16,
    stream_n=1 << 14, stream_tuner_runs=2,
    wave_pushes=6,
    serve_tenants=2, serve_sigs=2, serve_reqs=4,
    serve_n=1 << 12, serve_local=128,
    flash_bhd=(1, 2, 16), flash_T=(256,), flash_tiled_T=128,
    flash_dense_T=96, flash_oneshot_T=128, qkv_T=128,
    saxpy_n=1 << 10, backend_n=1 << 12, backend_nbody_n=256,
    backend_nbody_tiled=(32768, 24),
    affine_n=256, spmv_side=8, spmv_count_side=12, spmv_count_chunk=1 << 10,
    trace_iters=3,
)


@pytest.fixture(scope="module")
def devs():
    return platforms().cpus().subset(2)


def _check_rows(rows, n_min=1):
    assert len(rows) >= n_min
    for r in rows:
        assert {"name", "lowering", "cold_s", "run_s", "max_err"} <= set(r)
        assert r["cold_s"] >= 0.0 and r["run_s"] >= 0.0


def test_stage_compute(devs):
    rows = chip_smoke.stage_compute(devs, TOY)
    _check_rows(rows, 10)
    kl, hand, forced, nbody, wave, starts, shown, bfs, reduction, md = rows
    # CPU lanes take the XLA lowering by policy; the routing assertion
    # itself only binds on TPU lanes
    assert kl["lowering"] == "xla"
    # the hand kernel as users build it (lowering follows the lane: no
    # Mosaic call on a CPU lane) and with the lowering forced
    assert hand["name"] == "mandelbrot[hand Pallas] compute()"
    assert "interpret=True" in forced["name"]
    for r in (hand, forced):
        assert r["lowering"] == "python" and r["mosaic_calls"] == {"cpu": 0}
    for r in (kl, hand, forced):
        assert r["max_err"] == 0.0 and r["fused_windows"] > 0
        assert r["donate"] == [False, False]  # a TPU-lane property
    assert sum(kl["ranges_last"]) == 64 * 64
    # the fine-grained-marker window ran, exact, markers all retired
    assert kl["marker_window"]["donate"] == [False, False]
    assert kl["marker_window"]["reached"] > 0
    assert nbody["lanes"] == 2 and nbody["max_err"] <= 0.01
    # the wave step split by range: exact, every strip lane to lane
    assert wave["name"] == "wave compute() halo window"
    assert wave["lanes"] == 2 and sum(wave["ranges"]) == 64 * 64
    assert wave["halo_spans"] >= 11 * 2 and wave["max_err"] < 1e-5
    # three windows on one lane: the second and third start on the ladder
    assert starts["window_starts"] == {"first-sighting": 1, "ladder": 2}
    assert starts["ramp"] == ["x1", "x2"]  # a window of three computes
    # one frame a call on one lane: issued, landed, in the caller's array
    assert shown["name"] == "mandelbrot frame read back"
    assert shown["bytes"] == 4 * 64 * 64 and shown["max_err"] == 0.0
    assert shown["downloads"] == (shown["stream_chunks"] or 1)
    # Rodinia's BFS level by level: exact, two scatters, one byte a level
    assert bfs["name"] == "BFS traversal compute()" and bfs["max_err"] == 0.0
    assert bfs["lowering"] == "xla" and bfs["levels"] >= 4
    assert bfs["scatter"] == "stores:2;width:4+1"
    # the toy's launch is no wider than a chunk: nothing compactable
    assert bfs["compact"] == "" and bfs["level_ms"] > 0
    assert bfs["flag_bytes_up"] == bfs["flag_bytes_back"] == bfs["levels"]
    # SHOC's reduce: the group's tile by shifts and one broadcast, exact
    assert reduction["name"] == "group reduction compute()"
    assert reduction["max_err"] == 0.0 and reduction["lowering"] == "xla"
    assert reduction["local"] == ("arrays:1;bytes:1024;barriers:2;"
                                  "sites:shift:6,uniform:1,row:0")
    # the passes all its lanes make run with no mask (ISSUE 51)
    assert reduction["loops"] == "counted:1;masked:1;peeled:1"
    # its walk reads one window a group, not a row a work item (ISSUE 46),
    # and settles the windows once a launch, not a pass (ISSUE 47)
    assert reduction["access"] == ("slice:0;strided:0;uniform:0;gather:0;"
                                   "scatter:1;carried:0;local:7;group:2;"
                                   "settled:2")
    # SHOC's MD: float4 parameters, one access a vector, no scatter (ISSUE 50)
    assert md["name"] == "vector kernel compute()" and md["lowering"] == "xla"
    assert md["max_err"] < 1e-5
    assert md["vector"] == "params:2;width:4;loads:1;gathers:1;stores:1"
    assert md["access"] == ("slice:3;strided:0;uniform:0;gather:1;scatter:0;"
                            "carried:0")


def test_stage_compute_partitions_a_single_device():
    rows = chip_smoke.stage_compute(platforms().cpus().subset(1), TOY)
    assert rows[-6]["lanes"] == 2 and all(r > 0 for r in rows[-6]["ranges"])


def test_stage_transfers(devs):
    rows = chip_smoke.stage_transfers(devs, TOY)
    _check_rows(rows, 4)
    assert [r["name"].split()[1] for r in rows] == [
        "monolithic", "streamed", "pipeline", "pipeline"]
    assert all(r["max_err"] == 0.0 for r in rows)
    assert "tuner_chunks" in rows[1]


def test_stage_pipeline_one_stage_per_device(devs):
    rows = chip_smoke.stage_pipeline(devs, TOY)
    _check_rows(rows, 2)  # DevicePipeline + the ClPipeline chain
    assert all(r["max_err"] < 1e-3 for r in rows)
    assert "x2 chips" in rows[1]["name"]


def test_stage_serving(devs):
    (row,) = chip_smoke.stage_serving(devs, TOY)
    assert row["requests"] == 2 * 2 * 4
    assert row["launches"] < row["requests"] and row["coalesce_ratio"] > 1
    assert row["max_err"] == 0.0


def test_stage_kernels(devs):
    rows = chip_smoke.stage_kernels(devs, TOY)
    _check_rows(rows, 15)
    names = " | ".join(r["name"] for r in rows)
    for want in ("flash fwd+bwd T=256 highest", "flash fwd+bwd T=256 default",
                 "flash fwd T=128", "flash fwd T=96", "fused_qkv_attention",
                 "one-shot softmax", "ops.saxpy", "elementwise", "halo",
                 "SMEM uniform gather", "fitted tile (n-body)",
                 "__graft_entry__"):
        assert want in names, want
    by = {r["name"]: r for r in rows}
    assert by["flash fwd T=96"]["lowering"] == "dense"
    assert by["ops.saxpy"]["max_err"] == 0.0
    # the second call over the same arrays took its views as arguments
    assert by["affine mvt_kernel1"]["views"] == "kept:1;built:0"
    assert by["affine mvt_kernel2"]["views"] == "kept:0;built:0"
    spmv = by["views spmv"]
    assert spmv["views"] == "kept:2;built:0"  # a TPU lane keeps x's rows too
    assert sum(spmv["launch_bytes"]["kept"].values()) <= sum(
        spmv["launch_bytes"]["in_launch"].values())


def test_stage_trace_degrades_to_named_absence_off_chip(devs):
    (row,) = chip_smoke.stage_trace(devs, TOY)
    # the CPU backend exposes no device planes: the stage reports that by
    # name; on TPU lanes the same stage FAILS on zero device events
    assert row["n_events"] == 0
    assert isinstance(row["capture"], str) and "no device op" in row["capture"]


def test_interpreted_kernel_on_a_tpu_lane_is_a_failure():
    with pytest.raises(chip_smoke.SmokeFailure, match="ran interpreted"):
        chip_smoke._check_compiled("tpu", 0, "saxpy")
    chip_smoke._check_compiled("tpu", 3, "saxpy")
    chip_smoke._check_compiled("cpu", 0, "saxpy")  # the rule's other branch


def test_main_refuses_to_run_without_a_chip(capsys):
    assert os.environ.get("JAX_PLATFORMS") == "cpu"
    rc = chip_smoke.main([])
    cap = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in cap.err
    assert '"ok"' not in cap.out  # prints no result
