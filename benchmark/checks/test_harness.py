"""Checks of the benchmark itself, runnable by hand on the CPU container:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/checks -q

They drive ``run.run_cell`` — everything of a run but the look for a chip —
at sizes a test run can hold, on the host CPU.  Nothing here yields a device
number.  Three things are held:

- the sound program reads ``correct`` true in every configuration;
- the control — the configuration's reference computed in bfloat16, the
  nearest precision below the float32 it states, put in the program's place —
  fails at least one of the numbers compared (on the chip, at the cells' own
  sizes, the same was read on seeds 1, 2, 3: PERF.md section 2);
- with the timed path broken underneath (the kernel that the cruncher
  compiles drops part of its work or alters an answer), a whole run comes out
  with ``correct`` false;
- so does a run whose calls inside the window do nothing, with warm-up and
  the fresh call sound: what ``correct`` compares is what the window wrote.
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]

import cells  # noqa: E402
import run  # noqa: E402


def manifest_with_later_cells(manifest=cells.manifest) -> dict:
    """``BENCHMARK.json`` with the entries of ``later_cells.json`` pasted in:
    the cell that was proved and left out (PERF.md section 7) keeps its
    files, and they stay tested until a later PR admits it."""
    man = manifest()
    with open(os.path.join(HERE, "later_cells.json"), encoding="utf-8") as f:
        later = json.load(f)
    for key in ("configs", "workloads", "per_layer"):
        man[key] = man[key] + later[key]
    for extra in later["end_to_end"]:
        for m in man["end_to_end"]:
            if m["name"] == extra["name"]:
                m["workloads"] = m["workloads"] + extra["workloads"]
    return man


@pytest.fixture(autouse=True)
def later_cells(monkeypatch):
    monkeypatch.setattr(cells, "manifest", manifest_with_later_cells)

#: cell -> the sizes a CPU test can hold (widths of the data and the mix of
#: operations are the cells' own)
SMALL = {
    "nbody_8k_window": ({}, {"n": 512, "iterations_per_call": 5}),
    "mandelbrot_balance_4chip": (
        # 32 iterations: XLA's CPU backend contracts multiply-adds, which
        # moves the chaotic orbits of a few boundary pixels at 256 (6 of
        # 4096); the chip's kernel rounds as the reference does
        {"width": 64, "height": 64, "lanes": 2, "sample_blocks": 8,
         "local_range": 64, "max_iter": 32},
        {"n": 4096, "iterations_per_call": 3,
         "warmup_calls": 2, "warmup_quiet": {"calls": 1, "max_calls": 4}}),
    "triad_stream_1chip": ({"cruncher": {"streamed_transfers": True,
                                         "stream_chunks": 4}},
                           {"n": 1 << 14}),
}


def observed_for_control(cell, values, calls=40):
    """What a run hands ``compare``, without outputs: the control computes
    its own."""
    plan = (cell.ref.call_values(cell.cfg, cell.params, values)
            if hasattr(cell.ref, "call_values") else {})
    per_call = int(cell.params["iterations_per_call"])
    return {"iterations": 1 + calls * per_call, "outputs": None,
            "values": plan.get("cycle", [values])[-1], "ranges_log": [],
            "fresh": {"iterations": 1 + per_call, "outputs": None,
                      "values": plan.get("apart", values)}}

#: the same kernels with part of the work dropped or an answer altered
BROKEN = {
    "nbody_8k_window": ("vx[i] += ax * dt;", "vx[i] += 0.0f;"),
    "mandelbrot_balance_4chip": ("out[i] = (float)it;",
                                 "out[i] = (float)it + 1.0f;"),
    "triad_stream_1chip": ("c[i] = a[i] + s * b[i];", "c[i] = a[i] + b[i];"),
}


def small_cell(name: str) -> cells.Cell:
    cell = cells.load_cell(name)
    cfg, params = SMALL[name]
    return cell._replace(cfg={**cell.cfg, **cfg},
                         params={**cell.params, **params})


@pytest.fixture(scope="module")
def devices():
    from cekirdekler_tpu import hardware

    return hardware.chip_devices()  # the host CPU under JAX_PLATFORMS=cpu


def test_compute_kwargs_reach_compute(devices):
    """A later cell selects a pipeline engine from its configuration file."""
    cell = small_cell("triad_stream_1chip")
    cell = cell._replace(cfg={**cell.cfg, "compute_kwargs": {
        "pipeline": True, "pipeline_blobs": 4,
        "pipeline_type": "PIPELINE_EVENT"}})
    result = run.run_cell(cell, seed=3, seconds=0.2, trace=False,
                          devices=devices)
    assert result["correct"] is True


@pytest.mark.parametrize("name", sorted(SMALL))
def test_sound_program_is_correct(name, devices):
    result = run.run_cell(small_cell(name), seed=2**31 + 5, seconds=0.3,
                          trace=False, devices=devices)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {
        m["name"] for m in cells.load_cell(name).end_to_end}


@pytest.mark.parametrize("name", sorted(SMALL))
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bfloat16_control_fails(name, seed):
    cell = small_cell(name)
    rng = np.random.default_rng(seed)
    data, values = cell.ref.inputs(cell.cfg, cell.params, rng)
    compared = cell.ref.compare(cell.cfg, cell.params, data, values,
                                observed_for_control(cell, values), seed,
                                precision="bfloat16")
    assert not all(c.ok for c in compared), compared


@pytest.mark.parametrize("name", sorted(BROKEN))
def test_broken_timed_path_is_not_correct(name, devices, monkeypatch):
    good, bad = BROKEN[name]
    source = cells.kernel_source(cells.load_cell(name).cfg)
    assert good in source
    monkeypatch.setattr(cells, "kernel_source",
                        lambda cfg: source.replace(good, bad))
    result = run.run_cell(small_cell(name), seed=7, seconds=0.3, trace=False,
                          devices=devices)
    assert result["correct"] is False


@pytest.mark.parametrize("name", sorted(SMALL))
def test_window_of_idle_calls_is_not_correct(name, devices, monkeypatch):
    """Warm-up leaves sound bytes in every buffer; a window whose calls then
    do nothing must not be taken for one that worked."""
    real_window = run.window

    def idle_window(ctx, seconds, compiles):
        call, ctx.call = ctx.call, lambda: None
        try:
            real_window(ctx, seconds, compiles)
        finally:
            ctx.call = call

    monkeypatch.setattr(run, "window", idle_window)
    compared = []
    result = run.run_cell(small_cell(name), seed=11, seconds=0.05,
                          trace=False, devices=devices,
                          compared_out=compared)
    assert result["correct"] is False
    assert result["attempted"] >= 1 and compared


def test_result_carries_only_the_contracts_keys(devices):
    result = run.run_cell(small_cell("nbody_8k_window"), seed=5, seconds=0.1,
                          trace=False, devices=devices)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]  # the numbers compared last
    assert result["compared"] and all(
        set(c) == {"value", "limit"} and c["value"] <= c["limit"]
        for c in result["compared"].values())
    assert json.dumps(result, allow_nan=False)


def test_a_check_without_a_number_reads_null_in_the_line(devices, monkeypatch):
    """NaN is no JSON: a comparison that produced no number is ``null``
    beside its limit, and the run is not correct."""
    cell = small_cell("nbody_8k_window")
    nan = [cells.Compared("vel_step_rel_err", float("nan"), 1e-4)]
    monkeypatch.setattr(cell.ref, "compare", lambda *a, **k: nan)
    result = run.run_cell(cell, seed=5, seconds=0.05, trace=False,
                          devices=devices)
    assert result["correct"] is False
    assert result["compared"] == {"vel_step_rel_err": {"value": None,
                                                       "limit": 1e-4}}
    assert json.dumps(result, allow_nan=False)


@pytest.mark.parametrize("env, fixed", [
    ({}, True),
    ({"MALLOC_TRIM_THRESHOLD_": "131072"}, False),
    ({"GLIBC_TUNABLES": "glibc.malloc.mmap_threshold=65536"}, False),
])
def test_the_allocator_is_fixed_unless_the_caller_set_it(env, fixed):
    """In a process of its own (the thresholds are the whole process's): the
    harness fixes glibc's thresholds, and a caller's own setting stands.
    With them fixed a block of 4 MiB freed at the heap's top is not given
    back: allocating it again maps nothing (PERF.md section 6, PR 49)."""
    import subprocess

    code = ("import sys; sys.path.insert(0, sys.argv[1]); import run, ctypes\n"
            "print(run.steady_allocator())\n"
            "libc = ctypes.CDLL(None)\n"
            "libc.malloc.restype = ctypes.c_void_p\n"
            "libc.free.argtypes = [ctypes.c_void_p]\n"
            "a = libc.malloc(4 << 20); libc.free(a)\n"
            "b = libc.malloc(4 << 20); libc.free(b); print(a == b)\n")
    clean = {k: v for k, v in os.environ.items()
             if not (k.startswith("MALLOC_") or k == "GLIBC_TUNABLES")}
    out = subprocess.run([sys.executable, "-c", code, os.path.dirname(HERE)],
                         env={**clean, **env}, capture_output=True, text=True,
                         check=True).stdout.split()
    assert out[0] == str(fixed)
    if fixed:
        assert out[1] == "True"
