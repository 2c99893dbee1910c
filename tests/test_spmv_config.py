"""The ``hpcg_spmv`` configuration on the CPU rig: HPCG's sparse
matrix-vector product (27-point operator in CSR, one work-item a row) through
``ClArray`` / ``NumberCruncher.compute()`` against the configuration's plain
reference, which is computed from the grid and never from the CSR arrays.

The kernel and the reference are the benchmark's own files
(``benchmark/configs/hpcg_spmv.cl``, ``hpcg_spmv_ref.py``, ``hpcg_spmv.json``):
what the cell runs on the chip at 256^3 is what is held here at 16^3 and at a
grid with unequal sides, per call and in enqueue windows, on one lane and on
two with the rows split.  The rig proves results, flags and span fields,
never a time.
"""

import importlib.util
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import cekirdekler_tpu as ct
from cekirdekler_tpu.analysis import flag_row
from cekirdekler_tpu.arrays.clarray import ClArray
from cekirdekler_tpu.core.cruncher import NumberCruncher
from cekirdekler_tpu.kernel.registry import KernelProgram, lowering_meta

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs")
GRIDS = {"16^3": (16, 16, 16), "12x16x20": (12, 16, 20)}
LOCAL_RANGE = 256


def _load(name: str):
    spec = importlib.util.spec_from_file_location(
        "hpcg_spmv_ref_under_test", os.path.join(CONFIGS, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("hpcg_spmv_ref.py")
with open(os.path.join(CONFIGS, "hpcg_spmv.json"), encoding="utf-8") as _f:
    CFG = json.load(_f)
with open(os.path.join(CONFIGS, CFG["kernel_file"]), encoding="utf-8") as _f:
    SRC = _f.read()


def _cfg(grid) -> dict:
    nx, ny, nz = grid
    return {**CFG, "nx": nx, "ny": ny, "nz": nz}


def _arrays(cfg, seed: int, flags=None):
    """Host data from the configuration's own recipe, as ClArrays with the
    configuration's flags (or ``flags`` by array name in their place)."""
    n = cfg["nx"] * cfg["ny"] * cfg["nz"]
    data, _values = REF.inputs(cfg, {"n": n}, np.random.default_rng(seed))
    arrays = {
        spec["name"]: ClArray(data[spec["name"]], name=spec["name"],
                              **(flags or {}).get(spec["name"], spec["flags"]))
        for spec in cfg["arrays"]}
    return n, data, arrays


@pytest.mark.parametrize("lanes", [1, 2])
@pytest.mark.parametrize("mode", ["per_call", "window3"])
@pytest.mark.parametrize("grid", sorted(GRIDS))
def test_system_agrees_with_the_grid_reference(grid, mode, lanes):
    """Every row of ``y`` against the float64 product from the grid, with an
    alpha of its own in every call; 12 x 16 x 20 is 15 units of 256 rows:
    one rung on the lane that owns them all, ladders of several rungs at
    offsets of their own on two lanes with the rows split."""
    cfg = _cfg(GRIDS[grid])
    n, data, arrays = _arrays(cfg, seed=26)
    cr = NumberCruncher(ct.platforms().cpus().subset(lanes), SRC)
    first, *rest = arrays.values()
    group = first.next_param(*rest)
    try:
        if mode == "per_call":
            alphas = [2.0]
            group.compute(cr, 2600, "spmv", n, LOCAL_RANGE, values=(2.0,))
        else:
            alphas = [1.0, 4.0, 0.5]
            cr.enqueue_mode = True
            for alpha in alphas:
                for _ in range(3):
                    group.compute(cr, 2600, "spmv", n, LOCAL_RANGE,
                                  values=(alpha,))
                cr.barrier()
            cr.enqueue_mode = False
        assert cr.number_of_errors_happened == 0
        assert sum(cr.ranges_of(2600)) == n
        assert sum(r > 0 for r in cr.ranges_of(2600)) == lanes
    finally:
        cr.dispose()
    want = REF.product(cfg, data["x"], alphas[-1])
    got = np.asarray(arrays["y"])
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-6
    # the operands came back as they went
    assert np.array_equal(np.asarray(arrays["col"]), data["col"])


@pytest.mark.parametrize("grid", sorted(GRIDS))
def test_csr_times_ones_is_hpcgs_right_hand_side(grid):
    """HPCG's own check of the generated problem: ``A * ones`` is, row by
    row, 26 minus the row's off-diagonal entries."""
    cfg = _cfg(GRIDS[grid])
    a = REF.csr(cfg)
    n = cfg["nx"] * cfg["ny"] * cfg["nz"]
    per_row = np.diff(a["rowptr"])
    assert a["rowptr"][0] == 0 and a["rowptr"][-1] == a["col"].size
    assert a["col"].size == REF.nonzeros(cfg) and per_row.min() == 8
    assert per_row.max() == 27
    row_of = np.repeat(np.arange(n), per_row)
    times_ones = np.bincount(row_of, weights=a["val"], minlength=n)
    assert np.array_equal(times_ones, 26.0 - (per_row - 1))
    # one diagonal a row, columns ascending inside a row (row-major
    # neighbour order), all inside the grid
    assert np.array_equal(np.flatnonzero(a["col"] == row_of),
                          np.flatnonzero(a["val"] == 26.0))
    assert (a["val"] == 26.0).sum() == n
    inside_row = np.diff(row_of) == 0
    assert (np.diff(a["col"])[inside_row] > 0).all()
    assert a["col"].min() == 0 and a["col"].max() == n - 1


def test_plane_pattern_equals_the_dense_table():
    """The builder lays the middle plane's pattern down nz - 2 times; the
    dense [n, 27] table over the whole grid gives the same arrays."""
    a, b = REF.csr(_cfg((5, 4, 7))), REF._csr_dense(5, 4, 7)
    assert all(np.array_equal(a[k], b[k]) and a[k].dtype == b[k].dtype
               for k in ("rowptr", "col", "val"))


def _verdict(flags_by_name: dict, window: bool):
    rows = tuple(flag_row(ClArray(np.zeros(4, np.float32), **flags).flags)
                 for flags in flags_by_name.values())
    return KernelProgram(SRC).verify(("spmv",), rows, window=window)


@pytest.mark.parametrize("window", [False, True])
def test_ckprove_proves_the_configurations_flags(window):
    verdict = _verdict({s["name"]: s["flags"] for s in CFG["arrays"]}, window)
    assert verdict.ok and not verdict.errors


@pytest.mark.parametrize("gathered", ["col", "val", "x"])
def test_ckprove_names_partial_read_on_a_gathered_array(gathered):
    """``col``, ``val`` and ``x`` are read at indices the loop computes: only a
    whole read is sound."""
    flags = {s["name"]: dict(s["flags"]) for s in CFG["arrays"]}
    flags[gathered] = {"partial_read": True, "read_only": True}
    verdict = _verdict(flags, window=True)
    assert "partial-read-gather" in {f.kind for f in verdict.errors}


def test_a_tpu_build_is_vetoed_and_says_why():
    """Pallas refuses a per-lane gather; the launcher falls to the
    vectorized-XLA lowering and keeps the reason."""
    prog = KernelProgram(SRC)
    _fn, info = prog.launcher("spmv", 4096, LOCAL_RANGE, 4096, platform="tpu")
    assert info.lowering == "xla" and "lane-uniform" in info.veto
    # rows of unequal length: lanes leave the loop on different passes
    loops = "counted:0;masked:1"
    assert lowering_meta((info,)) == {"lowering": "xla", "loops": loops,
                                      "views": "kept:0;built:0",
                                      "veto": info.veto}
    _fn, info = prog.launcher("spmv", 4096, LOCAL_RANGE, 4096, platform="cpu")
    assert lowering_meta((info,)) == {"lowering": "xla", "loops": loops,
                                      "views": "kept:0;built:0"}


def test_launcher_hands_back_what_the_kernel_did_not_replace():
    """The executable returns ``y`` alone: the four operands come back as
    the very buffers that went in, not as copies."""
    import jax
    import jax.numpy as jnp

    cfg = _cfg(GRIDS["16^3"])
    n, data, _arrays_ = _arrays(cfg, seed=3)
    prog = KernelProgram(SRC)
    fn, info = prog.launcher("spmv", n, LOCAL_RANGE, n, platform="cpu")
    bufs = tuple(jnp.asarray(data[k]) for k in info.array_params)
    out = fn(0, bufs, (2.0,))
    assert [o is b for o, b in zip(out, bufs)] == [True] * 4 + [False]
    assert info.stored_params == ["y"]
    shapes = tuple(jax.ShapeDtypeStruct(b.shape, b.dtype) for b in bufs)
    lowered = fn.trace(jax.ShapeDtypeStruct((), jnp.int32), shapes,
                       (jax.ShapeDtypeStruct((), jnp.float32),)).lower()
    assert len(jax.tree_util.tree_leaves(lowered.out_info)) == 1
    want = REF.product(cfg, data["x"], 2.0)
    assert np.abs(np.asarray(out[4]) - want).max() < 1e-4


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One ``jax.profiler`` session on the CPU backend: a cold per-call
    compute, two enqueue windows of four on one lane, and the launcher a TPU
    lane would get run by hand.  Returns the ``ck/`` events of the dump's
    host plane."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    cfg = _cfg(GRIDS["16^3"])
    n, data, arrays = _arrays(cfg, seed=5)
    cr = NumberCruncher(ct.platforms().cpus().subset(1), SRC)
    first, *rest = arrays.values()
    group = first.next_param(*rest)
    trace_dir = str(tmp_path_factory.mktemp("spmv_spans"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        group.compute(cr, 2601, "spmv", n, LOCAL_RANGE, values=(1.0,))
        cr.enqueue_mode = True
        for _window in range(2):
            for _ in range(4):
                group.compute(cr, 2601, "spmv", n, LOCAL_RANGE, values=(2.0,))
            cr.barrier()
        cr.enqueue_mode = False
        fn, info = cr.cores.program.launcher("spmv", n, LOCAL_RANGE, n,
                                             platform="tpu")
        fn(0, tuple(jnp.asarray(data[k]) for k in info.array_params), (1.0,))
    finally:
        jax.profiler.stop_trace()
        cr.dispose()
    path = [os.path.join(r, f) for r, _d, fs in os.walk(trace_dir)
            for f in fs if f.endswith(".xplane.pb")][0]
    events = [SimpleNamespace(name=ev.name, stats=dict(ev.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events
              if ev.name.startswith("ck/")]
    return events


@pytest.mark.parametrize("kind", ["launch", "fused", "compile"])
def test_span_carries_the_lowering(profiled, kind):
    spans = [e for e in profiled if e.name == "ck/" + kind]
    assert spans, f"no ck/{kind} span in the session"
    if kind == "fused":
        # the ramp: x1 and the residue of the first window's two deferred
        # computes; the second window starts on the ladder: x1 x2, residue
        assert [str(e.stats["tag"]) for e in spans] == [
            "x1", "x1", "x1", "x2", "x1"]
        # the first window's spans close before its executable is traced
        assert "lowering" not in spans[0].stats
        spans = spans[2:]
    assert all(e.stats.get("lowering") == "xla" for e in spans)
    assert all(e.stats.get("loops") == "counted:0;masked:1" for e in spans)
    if kind == "launch":
        tags = [str(e.stats["tag"]) for e in spans]
        assert any(t.startswith("fused:spmv x") for t in tags)
        assert any(t == "spmv x1" for t in tags)


def test_compile_span_of_a_vetoed_tpu_build_carries_the_veto(profiled):
    vetoed = [e for e in profiled if e.name == "ck/compile"
              and str(e.stats["tag"]).endswith(" tpu")]
    # whole, with the reason's comma written ';' (it would end the value)
    assert len(vetoed) == 1 and vetoed[0].stats["veto"].endswith(
        "elementwise; statically shifted; nor lane-uniform (Pallas tile path)")
    # a CPU lane asked Pallas nothing: no veto to carry
    assert all("veto" not in e.stats for e in profiled
               if e.name == "ck/launch")
