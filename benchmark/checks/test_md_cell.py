"""``md_lj_step_1chip`` (configuration ``shoc_md``, loop ``md_step``) held to
what the other cells are held to, at 4096 atoms and 16 neighbours on the CPU
container (``JAX_PLATFORMS=cpu python3 -m pytest
benchmark/checks/test_md_cell.py -q``), and its readers held to reductions made
by hand.  Nothing here yields a device number.

- the sound program reads ``correct`` true through the loop with exactly the
  cell's end-to-end metrics: a call writes ITS frame of positions and computes
  with ITS ``(lj1, lj2)``, the neighbour list crosses once;
- each fault ``limits_why`` names reads ``correct`` false: a neighbour
  dropped, the previous call's ``(lj1, lj2)``, the previous call's positions (a
  skipped upload), ``f.w`` left unwritten, a force array never read back, the
  ``if`` turned round, a window of idle calls, the bfloat16 control; and what
  it cannot see, stated as a test: the ``if`` taken OUT, the farthest neighbour
  dropped (both under float32's rounding of a sum the nearest pairs dominate);
- the data recipe keeps what SHOC's fixes; ``kernel_cost`` and the readers on
  spans and operations made by hand;
- the configuration, the cell and every new entry are in the manifest, found
  BY NAME (a later PR appends behind them).
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

CELL, CONFIG = "md_lj_step_1chip", "shoc_md"
NEW_METRICS = [
    "md_kernel_ms_per_call", "md_roofline", "md_gather_share",
    "vector_accesses", "scattered_accesses.md", "upload_bytes_per_call.md",
    "stream_chunks.md", "device_idle_share.md", "launch_ms_per_call.md"]
SIDE, K = 16, 16
N = SIDE ** 3
SMALL_CFG = {"atoms": N, "lattice": [SIDE] * 3, "neighbours": K}
SMALL_TRAFFIC = {"n": N}


def small_cell(**traffic) -> cells.Cell:
    cell = cells.load_cell(CELL)
    return cell._replace(cfg={**cell.cfg, **SMALL_CFG},
                         params={**cell.params, **SMALL_TRAFFIC, **traffic})


@pytest.fixture(scope="module")
def devices():
    from cekirdekler_tpu import hardware

    return hardware.chip_devices()  # the host CPU under JAX_PLATFORMS=cpu


def run_small(devices, seed=2**31 + 48, seconds=0.3, **traffic):
    compared = []
    result = run.run_cell(small_cell(**traffic), seed=seed, seconds=seconds,
                          trace=False, devices=devices, compared_out=compared)
    return result, compared


# -- the program through the loop, against the reference --------------------

def logged(monkeypatch) -> list:
    """``(the loop's log, calls of the window, the list's read flag)`` as
    ``read_back`` leaves them."""
    logs = []
    real = run.read_back

    def read_back(ctx):
        out = real(ctx)
        logs.append((list(ctx.data["calls"]), len(ctx.walls),
                     ctx.arrays["neighList"].read))
        return out

    monkeypatch.setattr(run, "read_back", read_back)
    return logs


def test_sound_program_is_correct_with_exactly_the_cells_metrics(
        devices, monkeypatch):
    logs = logged(monkeypatch)
    result, compared = run_small(devices)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3
    assert set(result["metrics"]) == {"call_p50_ms", "setup_s"}
    by = {c.name: c for c in compared}
    assert list(by) == ["force_window_rel_err", "force_fresh_rel_err",
                        "atoms_unwritten", "w_nonzero"]
    assert 0 < by["force_window_rel_err"].value < 1e-5
    assert 0 < by["force_fresh_rel_err"].value < 1e-5
    assert by["atoms_unwritten"].value == by["w_nonzero"].value == 0.0
    # the harness's first compute logs nothing; warm-up takes the cycle once
    # and ends on the frame set apart; the window goes on through the cycle;
    # the fresh call is the one apart again
    (log, calls, list_read), = logs
    pairs = [tuple(lj) for lj in small_cell().ref.LJ_CYCLE]
    apart = (4, *small_cell().ref.LJ_APART)
    assert log[:5] == [(k, *pairs[k]) for k in range(4)] + [apart]
    assert log[5:-1] == [(k % 4, *pairs[k % 4]) for k in range(calls)]
    assert log[-1] == apart and len(log) == 5 + calls + 1
    assert list_read is False  # it crossed with the first compute, and stays


# -- what must fail ---------------------------------------------------------

def made(seed=3):
    cell = small_cell()
    data, values = cell.ref.inputs(cell.cfg, cell.params,
                                   np.random.default_rng(seed))
    return cell, data, values, cell.ref.call_values(cell.cfg, cell.params, values)


def sound(cell, data, values, frame, **kw):
    """``force3`` as a sound call leaves it: frame ``frame``, ``values``."""
    _k, cutsq, lj1, lj2, _n = values
    f = cell.ref.forces(data["frames"][frame].reshape(N, 4),
                        data["neighList"].reshape(K, N), np.arange(N),
                        cutsq, lj1, lj2, **kw)
    out = np.zeros((N, 4), np.float32)
    out[:, :3] = f
    return out.reshape(-1)


def compare(cell, data, window, fresh, **kw):
    """``window`` / ``fresh``: ``(values, force3)`` of the two calls compared."""
    observed = {"values": tuple(window[0]), "outputs": {"force3": window[1]},
                "fresh": {"values": tuple(fresh[0]),
                          "outputs": {"force3": fresh[1]}}}
    got = cell.ref.compare(cell.cfg, cell.params, data, tuple(window[0]),
                           observed, 1, **kw)
    return {c.name: c for c in got}


@pytest.fixture(scope="module")
def calls():
    """The two calls ``compare`` looks at, as a sound program leaves them."""
    cell, data, values, plan = made()
    last, apart = plan["cycle"][2], plan["apart"]
    return SimpleNamespace(
        cell=cell, data=data, values=values, plan=plan, last=last, apart=apart,
        window=(last, sound(cell, data, last, 2)),
        fresh=(apart, sound(cell, data, apart, 4)))


def test_compare_passes_the_sound_calls(calls):
    assert calls.values == (K, 16.0, 1.5, 2.0, N)
    assert (calls.data["force3"] == -1).all()
    assert calls.data["position"].shape == (4 * N,)
    ok = compare(calls.cell, calls.data, calls.window, calls.fresh)
    assert all(c.ok for c in ok.values())
    assert ok["force_window_rel_err"].value < 1e-6  # float32 of a float64 sum


def repeated_pass(c, drop: int, keep: int):
    """The forces of a walk whose pass ``drop`` never ran and whose pass
    ``keep`` ran twice."""
    short = dict(c.data, neighList=c.data["neighList"].copy())
    short["neighList"].reshape(K, N)[drop] = short["neighList"].reshape(K, N)[keep]
    return sound(c.cell, short, c.last, 2)


def holed(c, rows: slice, columns):
    out = c.window[1].copy()
    out.reshape(N, 4)[rows, columns] = -1.0
    return out


W, F = "force_window_rel_err", "force_fresh_rel_err"
# fault -> (the window's force3, the fresh call's force3 or None for the sound
# one, the numbers that must read not ok)
FAULTS = {
    # the walk's first pass (the nearest) never ran, the second ran twice
    "the nearest neighbour dropped": (lambda c: repeated_pass(c, 0, 1), None, {W}),
    # at 16 neighbours; of 128 the farthest is under the rounding (below)
    "the last of 16 neighbours dropped": (
        lambda c: repeated_pass(c, -1, -2), None, {W}),
    "the previous call's (lj1, lj2)": (
        lambda c: sound(c.cell, c.data, c.plan["cycle"][1], 2), None, {W}),
    # a skipped upload: this call's pair on the frame before
    "the previous call's positions": (
        lambda c: sound(c.cell, c.data, c.last, 1), None, {W}),
    # the pairs inside the cutoff are left out
    "the if turned round": (lambda c: np.zeros(4 * N, np.float32), None, {W}),
    "f.w unwritten": (lambda c: holed(c, slice(None), 3), None, {"w_nonzero"}),
    "a lane's share of the write-back missing": (
        lambda c: holed(c, slice(N // 2, None), slice(None)), None,
        {W, "atoms_unwritten", "w_nonzero"}),
    "a force array never read back": (
        lambda c: c.window[1], lambda c: np.full(4 * N, -1, np.float32),
        {F, "atoms_unwritten", "w_nonzero"}),
    "the window's result in the fresh call's place": (
        lambda c: c.window[1], lambda c: c.window[1], {F}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_compare_fails_each_named_fault(calls, fault):
    window, fresh, by = FAULTS[fault]
    got = compare(
        calls.cell, calls.data,
        (calls.last, np.asarray(window(calls), np.float32)),
        calls.fresh if fresh is None else (calls.apart, fresh(calls)))
    assert {k for k, c in got.items() if not c.ok} == by, got
    if fault == "the previous call's positions":
        assert got[W].value > 1e-3  # 0.01 spacings matter
    if fault == "f.w unwritten":
        assert got["w_nonzero"].value == N and got["atoms_unwritten"].value == 0
    if fault == "a lane's share of the write-back missing":
        assert got["atoms_unwritten"].value == N // 2
    if fault == "a force array never read back":
        assert got["atoms_unwritten"].value == N


def test_the_if_taken_out_is_under_float32s_rounding(calls):
    """What the cell CANNOT see: a wrapped neighbour lies a box away and adds
    r^-8 of it, under a billionth of the largest force."""
    got = compare(calls.cell, calls.data, (calls.last, sound(
        calls.cell, calls.data, calls.last, 2, without_cutoff=True)), calls.fresh)
    assert got[W].ok and got[W].value < 1e-6


def test_the_control_stands_in_the_programs_place_and_is_not_correct(calls):
    control = compare(calls.cell, calls.data, calls.window, calls.fresh,
                      precision="bfloat16")
    assert control[W].value > 0.05 and control[F].value > 0.05
    assert not control[W].ok and not control[F].ok
    with pytest.raises(ValueError):
        compare(calls.cell, calls.data, calls.window, calls.fresh,
                precision="float16")


def test_the_farthest_of_128_neighbours_is_under_float32s_rounding():
    """What the cell CANNOT see (PERF.md section 7): the force is r^-14 steep,
    so the nearest pairs make the sum, and the 128th neighbour, sqrt(10)
    spacings away, adds less than float32 rounds off it."""
    cell = small_cell()
    cfg = {**cell.cfg, "atoms": 512, "lattice": [8, 8, 8], "neighbours": 128}
    data, values = cell.ref.inputs(cfg, {"n": 512}, np.random.default_rng(4))
    neigh = data["neighList"].reshape(128, 512)
    pos, atoms = data["frames"][0].reshape(512, 4), np.arange(512)
    whole = cell.ref.forces(pos, neigh, atoms, *values[1:4])
    short = neigh.copy()
    short[-1] = short[-2]
    less = cell.ref.forces(pos, short, atoms, *values[1:4])
    assert 0 < np.abs(less - whole).max() / np.abs(whole).max() < 1e-6


def test_the_program_with_the_if_turned_round_is_not_correct(devices, monkeypatch):
    real = cells.kernel_source
    monkeypatch.setattr(cells, "kernel_source", lambda cfg: real(cfg).replace(
        "if (r2inv < cutsq) {", "if (r2inv >= cutsq) {"))
    result, compared = run_small(devices)
    assert result["correct"] is False
    by = {c.name: c for c in compared}
    assert not by["force_window_rel_err"].ok and not by["force_fresh_rel_err"].ok
    assert by["atoms_unwritten"].ok and by["w_nonzero"].ok


def test_the_program_with_the_if_taken_out_reads_correct(devices, monkeypatch):
    """What the cell CANNOT see (PERF.md section 7, limits_why): both sides of
    the branch run (the recipe's wrapped neighbours fail the test), but a pair
    a box apart adds r^-8 of the largest force, far under float32's rounding
    of the sum, so a kernel without the ``if`` gives the same forces."""
    real = cells.kernel_source
    monkeypatch.setattr(cells, "kernel_source", lambda cfg: real(cfg).replace(
        "if (r2inv < cutsq) {", "if (r2inv < cutsq * 1.0e6f) {"))
    result, compared = run_small(devices)
    assert result["correct"] is True
    assert {c.name: c for c in compared}["force_window_rel_err"].value < 1e-5


def test_the_program_that_leaves_w_unwritten_is_not_correct(devices, monkeypatch):
    """``float4 f;`` with three components set: the language zeroes a local
    (OpenCL leaves it undefined), so the fault is put where it shows."""
    real = cells.kernel_source
    monkeypatch.setattr(cells, "kernel_source", lambda cfg: real(cfg).replace(
        "force3[idx] = f;", "f.w = 7.0f; force3[idx] = f;"))
    result, compared = run_small(devices)
    by = {c.name: c for c in compared}
    assert result["correct"] is False and by["w_nonzero"].value == 2 * N
    assert by["force_window_rel_err"].ok


def test_a_skipped_upload_is_not_correct(devices, monkeypatch):
    """Every compute sees the frame of the call before it: what a loop that
    skipped the positions' upload would compute on.  (Clearing the array's
    ``read`` flag shows nothing on a host-CPU lane, whose buffer may BE the
    host array; so the stale frame is put there by hand.)"""
    loop = cells.load_module("loops", "md_step.py")
    real = loop.make_call

    def make_call(ctx):
        call, compute = real(ctx), ctx.compute
        host = ctx.arrays["position"].host()
        stale = [host.copy()]

        def late() -> None:
            fresh = host.copy()
            host[:] = stale[0]
            stale[0] = fresh
            compute()

        ctx.compute = late
        return call

    cell = small_cell()
    monkeypatch.setattr(cell.loop, "make_call", make_call)
    compared = []
    result = run.run_cell(cell, seed=5, seconds=0.3, trace=False,
                          devices=devices, compared_out=compared)
    by = {c.name: c for c in compared}
    assert result["correct"] is False
    assert not by["force_window_rel_err"].ok and not by["force_fresh_rel_err"].ok
    assert by["atoms_unwritten"].ok and by["w_nonzero"].ok


def test_a_window_of_idle_calls_is_not_correct(devices, monkeypatch):
    """Warm-up's last call left the forces of the frame and the pair set
    apart; a window whose calls compute nothing leaves them under the last
    call's arguments."""
    real_window = run.window

    def idle_window(ctx, seconds, compiles):
        call = ctx.call
        ctx.call = lambda: None
        try:
            real_window(ctx, seconds, compiles)
        finally:
            ctx.call = call

    monkeypatch.setattr(run, "window", idle_window)
    result, compared = run_small(devices)
    by = {c.name: c for c in compared}
    assert result["correct"] is False
    assert not by["force_window_rel_err"].ok and by["force_fresh_rel_err"].ok


# -- the list is uploaded once and stays; the positions cross every call -----

def without_residency(cell: cells.Cell) -> cells.Cell:
    """The configuration without ``after_first_upload`` on the list."""
    arrays = [{k: v for k, v in spec.items() if k != "after_first_upload"}
              for spec in cell.cfg["arrays"]]
    return cell._replace(cfg={**cell.cfg, "arrays": arrays})


def span(kind: str, start: float, ms: float, lane: int = 0, **stats):
    return host_phases.HostSpan(
        kind, start, start + 1e-3 * ms, 1,
        {"lane": lane, **stats} if kind.startswith("ck/") else {})


def spans_of_a_run(devices, monkeypatch, cell):
    """The spans the upload reader goes by, of one small run on the CPU:
    ``bench/call`` from the harness's own span sites, ``ck/upload`` (with the
    bytes the program's span carries) and ``ck/launch`` around the lane's two
    methods.  ``(lines, window start, window end, result, reads)``: ``reads``
    is ``neighList.read`` as every upload or launch found it."""
    import contextlib
    import time

    from cekirdekler_tpu.core.worker import Worker

    caller, lane, reads = [], [], []

    @contextlib.contextmanager
    def bench_span(name):
        t0 = time.perf_counter()
        yield
        caller.append(host_phases.HostSpan(name, t0, time.perf_counter(), 0,
                                           {}))

    def around(method, kind, stats):
        real = getattr(Worker, method)

        def wrapped(self, *args, **kwargs):
            t0 = time.perf_counter()
            try:
                return real(self, *args, **kwargs)
            finally:
                reads.append((kind, arrays["neighList"].read))
                lane.append(host_phases.HostSpan(
                    kind, t0, time.perf_counter(), 1,
                    {"lane": self.index, **stats(*args)}))

        monkeypatch.setattr(Worker, method, wrapped)

    around("upload", "ck/upload", lambda arr, off, size, full: {
        "tag": arr.name,
        "bytes": arr.host().nbytes if full else size * arr.host().itemsize})
    around("launch", "ck/launch", lambda *args: {})
    real_build, arrays = run.build, {}

    def build(*args, **kwargs):
        ctx = real_build(*args, **kwargs)
        ctx.span = bench_span
        # one chunk: the uploads of a chunked call go by another method
        ctx.cr.stream_chunks = 1
        arrays.update(ctx.arrays)
        return ctx

    monkeypatch.setattr(run, "build", build)
    result = run.run_cell(cell, seed=2**31 + 50, seconds=0.2, trace=False,
                          devices=devices)
    return ([caller, lane], caller[0].start, caller[-1].end, result, reads)


def upload_bytes_per_call(lines, t0, t1):
    r = cells.load_reader("levels_per_call").reduce(lines, t0, t1, 0)
    return cells.load_reader("upload_bytes_per_call.md").read(
        SimpleNamespace(traversals=r))


def test_the_loop_turns_read_off_after_the_first_upload_and_the_list_stays(
        devices, monkeypatch):
    lines, t0, t1, result, reads = spans_of_a_run(devices, monkeypatch,
                                                  small_cell())
    assert result["correct"] is True
    # the harness's one synchronous compute finds the flag on and uploads the
    # list whole, once in the process; every later upload and launch finds it
    # off, and only the positions cross
    assert reads[0] == ("ck/upload", True) and ("ck/launch", True) in reads[:4]
    first_launch = reads.index(("ck/launch", True))
    assert all(read is False for _kind, read in reads[first_launch + 1:])
    (up,) = [s for s in lines[1] if s.stats.get("tag") == "neighList"]
    assert up.end <= t0 and up.stats["bytes"] == 4 * K * N
    inside = [s for s in lines[1] if s.name == "ck/upload" and s.start >= t0]
    assert inside and {s.stats["tag"] for s in inside} == {"position"}
    assert upload_bytes_per_call(lines, t0, t1) == 16.0 * N


def test_a_list_uploaded_again_at_every_call_reads_its_bytes(
        devices, monkeypatch):
    """Only where the configuration says so: without the entry the loop leaves
    ``read`` on, the program uploads the list at every synchronous compute,
    and ``upload_bytes_per_call.md`` reads its bytes on top of the frame's."""
    lines, t0, t1, result, reads = spans_of_a_run(
        devices, monkeypatch, without_residency(small_cell()))
    assert result["correct"] is True  # the same forces, by the dearer road
    assert all(read is True for _kind, read in reads)
    assert upload_bytes_per_call(lines, t0, t1) == 16.0 * N + 4.0 * K * N
    # one upload of it in two calls would already show: the reader by hand
    calls = [span("bench/call", 10.0, 40), span("bench/call", 10.1, 40)]
    lane = [span("ck/upload", 10.001, 1, tag="position", bytes=2**25),
            span("ck/launch", 10.01, 1),
            span("ck/upload", 10.101, 1, tag="position", bytes=2**25),
            span("ck/launch", 10.11, 1),
            span("ck/upload", 10.105, 1, tag="neighList", bytes=2**30)]
    assert upload_bytes_per_call([calls, lane], 10.0, 10.2) == 2.0**25 + 2.0**29
    assert upload_bytes_per_call([calls, lane[:4]], 10.0, 10.2) == 2.0**25
    # a program without the spans leaves nothing to read, not 0
    assert cells.load_reader("upload_bytes_per_call.md").read(
        SimpleNamespace(traversals=None)) is None


def test_the_vector_field_by_hand():
    """``vector_accesses``: the three counts of the lane's first launch inside
    the window that carries the field; a program without it (the parent)
    leaves nothing to read."""
    reader = cells.load_reader("vector_accesses")
    field = "params:2;width:4;loads:1;gathers:1;stores:1"
    lane = [span("ck/launch", 9.0, 1, vector="params:9;width:2;loads:9;"
                 "gathers:9;stores:9"),           # before the window
            span("ck/launch", 10.2, 1, lane=1, vector=field.replace("1", "7")),
            span("ck/launch", 10.3, 1),            # no field
            span("ck/launch", 10.4, 1, vector=field),
            span("ck/launch", 10.5, 1, vector="params:1;width:2+4;loads:5;"
                 "gathers:0;stores:0")]
    got = reader.vector_field([lane], 10.0, 14.0, 0)
    assert got == {"params": 2, "width": 4, "loads": 1, "gathers": 1,
                   "stores": 1}
    assert reader.read(SimpleNamespace(vector_field=got)) == 3.0
    assert reader.vector_field([lane[:3]], 10.0, 14.0, 0) is None
    assert reader.read(SimpleNamespace(vector_field=None)) is None
    # two widths: counted all the same
    mixed = reader.vector_field([lane[4:]], 10.0, 14.0, 0)
    assert reader.read(SimpleNamespace(vector_field=mixed)) == 5.0
    # the scatter count of the same launch's ``access`` field (the variant)
    r = SimpleNamespace(access="slice:3;strided:0;uniform:0;gather:1;"
                        "scatter:0;carried:0")
    assert cells.load_reader("scattered_accesses.md").read(
        SimpleNamespace(traversals=r)) == 0.0


# -- the data recipe ---------------------------------------------------------

def test_the_recipe_keeps_what_shocs_fixes():
    cell, data, values, plan = made(seed=9)
    ref = cell.ref
    off = ref.offsets(128)
    assert len({tuple(o) for o in off}) == 128 and not (off == 0).all(axis=1).any()
    r2 = (off.astype(int) ** 2).sum(axis=1)
    assert (np.diff(r2) >= 0).all() and r2[0] == 1 and r2[121] == 9 and r2[-1] == 10
    assert [tuple(o) for o in off[:3]] == [(-1, 0, 0), (0, -1, 0), (0, 0, -1)]
    a = cell.cfg["spacing"]
    pos = data["frames"].reshape(5, N, 4)
    neigh = data["neighList"].reshape(K, N)
    assert not pos[..., 3].any() and data["position"].tolist() == pos[0].reshape(-1).tolist()
    d = np.linalg.norm(pos[0][:, None, :3] - pos[0][neigh.T][:, :, :3], axis=2)
    assert d.min() >= 0.7 * a - 1e-6          # no pair closer than 0.7 spacings
    inside = (d * d < cell.cfg["cutsq"]).mean()
    assert 0.5 < inside < 1.0                  # both sides of the branch run
    assert (neigh != np.arange(N)).all()       # no atom lists itself
    # labels say nothing about place: neighbouring labels lie far apart
    assert np.linalg.norm(np.diff(pos[0][:, :3], axis=0), axis=1).mean() > 3 * a
    # the frames differ by at most 0.01 spacings an axis, and do differ
    moved = np.abs(pos[1:, :, :3] - pos[0, :, :3])
    assert 0 < moved.max() <= 2 * 0.01 * a + 1e-6
    assert len(plan["cycle"]) == 4 and plan["apart"] not in plan["cycle"]
    assert plan["cycle"][0] == [K, 16.0, 1.5, 2.0, N]  # SHOC's pair first
    assert len({(lj1, lj2) for _k, _c, lj1, lj2, _n
                in plan["cycle"] + [plan["apart"]]}) == 5
    # the same seed, the same atoms
    again, _ = ref.inputs(cell.cfg, cell.params, np.random.default_rng(9))
    assert all(np.array_equal(again[k], data[k]) for k in ("frames", "neighList"))
    with pytest.raises(ValueError):
        ref.inputs({**cell.cfg, "lattice": [SIDE, SIDE, SIDE + 1]}, cell.params,
                   np.random.default_rng(9))


# -- kernel_cost and the readers against reductions made by hand -------------

def test_kernel_cost_is_the_least_traffic_of_the_work():
    cell = cells.load_cell(CELL)
    n = cell.cfg["atoms"]
    assert cell.ref.kernel_cost(cell.cfg, cell.params, n) == {
        "ops": 22 * 128 * n, "bytes": (4 * 128 + 16 * 128 + 32) * n}
    # 5.4 GB at the chip's 819 GB/s: 6.6 ms (where a gather fetched 16 bytes)
    peak = cells.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    least = cell.ref.kernel_cost(cell.cfg, cell.params, 2097152)["bytes"]
    assert 1e3 * least / peak == pytest.approx(6.637, abs=1e-3)


GATHER = ("%fusion.23 = s32[2048,128]{1,0} fusion(s32[65537,128]{1,0} "
          "%bitcast.4, s32[2048]{0} %add.7), kind=kCustom, "
          "calls=%fused_computation.9")
PICK = ("%select_reduce_fusion.5 = s32[4,2048]{1,0} fusion(s32[2048,128]{1,0} "
        "%fusion.23, s32[2048]{0} %and.3), kind=kInput, "
        "calls=%fused_computation.23")
OTHER = ("%compare_select_fusion.8 = f32[4,2048]{1,0} fusion(f32[4,2048]{1,0} "
         "%get-tuple-element.31, f32[] %fusion.230), kind=kLoop, "
         "calls=%fused_computation.fusion.23")
LOOP = ("%while.7 = (s32[], s32[2048,128]{1,0}) while((s32[], "
        "s32[2048,128]{1,0}) %fusion.23), condition=%cond, body=%body")


def test_the_gather_and_its_picks_by_hand():
    """The row fetch AND the operations that read the fetched rows; an
    operation that reads another result (``%fusion.230``, or a name in its
    ``calls=``) and a container around them are neither."""
    reader = cells.load_reader("md_gather_share")
    assert reader.operands(PICK) == {"fusion.23", "and.3"}
    assert reader.operands(OTHER) == {"get-tuple-element.31", "fusion.230"}
    events = [(GATHER, 10.0, 10.5), (PICK, 10.5, 10.9), (OTHER, 10.9, 11.0),
              (LOOP, 10.0, 11.0), (GATHER, 13.8, 14.4), (PICK, 9.0, 9.9)]
    assert reader.gather_and_pick_seconds(events, 10.0, 14.0) == pytest.approx(
        0.5 + 0.4 + 0.2)
    assert reader.gather_and_pick_seconds([(OTHER, 10.0, 11.0)], 10.0, 14.0) == 0


OPS = {("fusion.9", "fusion"): 0.5, ("gather_fusion", "fusion"): 1.25,
       ("copy.3", "copy"): 0.25, ("while.1", "while"): 1.9}


def by_hand() -> SimpleNamespace:
    cell = small_cell()
    reduced = xplane.Reduced(
        t0=10.0, t1=14.0, busy_s={0: 3.0}, op_seconds={0: dict(OPS)},
        op_counts={0: {k: 4 for k in OPS}}, idle_by_span={0: {}}, calls=4)
    return SimpleNamespace(
        cell=cell, cfg=cell.cfg, params=cell.params, n=N, reduced=reduced,
        window_compiles=0,
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def read(metric: str, ctx):
    return cells.load_reader(metric).read(ctx)


def test_the_readers_by_hand():
    ctx = by_hand()
    # the container left out, the copy counted: 2.0 s over four computes
    assert read("md_kernel_ms_per_call", ctx) == pytest.approx(500.0)
    assert read("device_idle_share.md", ctx) == pytest.approx(25.0)
    least = (4 * K + 16 * K + 32) * N
    assert read("md_roofline", ctx) == pytest.approx(
        100.0 * 4 * least / 819e9 / 2.0)
    assert 0 < read("md_roofline", ctx) < 100
    ctx.reduced = ctx.reduced._replace(op_seconds={0: {}})
    assert read("md_kernel_ms_per_call", ctx) is None
    assert read("md_roofline", ctx) is None
    assert read("md_gather_share", ctx) is None  # before any trace is looked for


# -- the manifest, by name --------------------------------------------------

def test_the_configuration_the_cell_and_its_metrics_are_in_the_manifest():
    man = cells.manifest()
    row = next(w for w in man["workloads"] if w["name"] == CELL)
    assert row == {**row, "config": CONFIG, "traffic": CELL, "chips": 1}
    conf = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == [] and conf["file"] == (
        f"benchmark/configs/{CONFIG}.json")
    assert all(len(s) <= 200 for s in (row["why"], conf["why"], conf["source"]))
    listed = {m["name"]: m for m in man["per_layer"]}
    assert set(NEW_METRICS) <= set(listed) and len(listed) <= 128  # the contract's most
    assert all(listed[m]["workloads"] == [CELL]
               and listed[m]["moves"] == "call_p50_ms" for m in NEW_METRICS)
    assert listed["md_roofline"]["unit"] == "%"
    assert {listed[m]["source"] for m in NEW_METRICS[:3]} == {"device_trace"}
    cell = cells.load_cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == ["call_p50_ms", "setup_s"]
    assert set(NEW_METRICS) <= {m["name"] for m in cell.per_layer}
    for m in NEW_METRICS:
        assert cells.load_reader(m) is not None
    e2e = next(m for m in man["end_to_end"] if m["name"] == "call_p50_ms")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.01
    assert not any(CELL in m.get("workloads", ()) for m in man["end_to_end"]
                   if m["name"] not in ("call_p50_ms", "setup_s"))
    # the configuration: the source's shapes, the scale the rule gives
    cfg = cell.cfg
    assert cfg["source"] == conf["source"] and cfg["reduced"] == []
    assert cfg["atoms"] in (2097152, 1048576)  # ISSUE 50's two sizes
    assert cfg["lattice"] in ([128, 128, 128], [128, 128, 64])
    assert (cfg["neighbours"], cfg["cutsq"], cfg["lj1"], cfg["lj2"],
            cfg["local_range"], cfg["lanes"]) == (128, 16.0, 1.5, 2.0, 256, 1)
    assert {"atoms", "data", "source_files"} <= set(cfg["assumed"])
    flags = {s["name"]: s["flags"] for s in cfg["arrays"]}
    assert list(flags) == ["force3", "position", "neighList"]
    assert flags["force3"] == {"read": False, "write": True,
                               "elements_per_work_item": 4}
    assert flags["position"]["elements_per_work_item"] == 4
    assert flags["position"]["read"] and not flags["position"].get("partial_read")
    assert flags["neighList"] == {"read_only": True}
    # the list stays on the chip: the loop's ``enter`` turns its read flag off
    # after the harness's one synchronous compute, and no other array's
    assert [(s["name"], s.get("after_first_upload")) for s in cfg["arrays"]] == [
        ("force3", None), ("position", None), ("neighList", {"read": False})]
    assert cfg["fresh_call"] == {"fill_role": "output", "fill_value": -1,
                                 "upload": False}
    assert cfg["limits"] == {"force_rel_err": 1e-4, "atoms_unwritten": 0,
                             "w_nonzero": 0}
    assert cell.params["n"] == cfg["atoms"] and cell.params["loop"] == "md_step"
    assert cell.params["iterations_per_call"] == 1
    assert cell.params["warmup_calls"] == 4 and cell.params["pins"] == {}
    # pinned (ISSUE 50): the free tuner keys on (lj1, lj2) and compiled its
    # chunked rungs inside windows (PR 50's unpinned runs); the count is the
    # sweep's, written into assumed.stream_chunks
    assert cfg["cruncher"] == {"stream_chunks": 32}
    assert "stream_chunks" in cfg["assumed"]
    assert cell.params["trace_seconds"] == 20
    # the kernel is the source's, letter for letter, with its float4s
    text = cells.kernel_source(cfg)
    assert text.count("__kernel void") == 1 and "compute_lj_force" in text
    for line in ("__global float4 *force3, __global float4 *position,",
                 "float4 ipos = position[idx];",
                 "float4 f = {0.0f, 0.0f, 0.0f, 0.0f};",
                 "int jidx = neighList[j * inum + idx];",
                 "float4 jpos = position[jidx];",
                 "if (r2inv < cutsq) {",
                 "float force = r2inv * r6inv * (lj1 * r6inv - lj2);",
                 "f.x += delx * force;  f.y += dely * force;  f.z += delz * force;",
                 "force3[idx] = f;"):
        assert line in text, line
    # the reference is plain numpy: it imports nothing of the program
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           cfg["reference"]), encoding="utf-8") as f:
        assert "cekirdekler" not in f.read()


def test_the_accepted_cells_report_what_they_reported():
    """By name, whatever this PR appended behind them."""
    for name in ("mandelbrot_percall_1chip", "mvt_16k_window",
                 "bfs_1m_traversal_1chip", "reduce_1gib_percall_1chip",
                 "spmv_hpcg256_window", "nbody_8k_window"):
        cell = cells.load_cell(name)
        assert not [m for m in cell.per_layer if m["name"] in NEW_METRICS]
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
    bfs = {m["name"] for m in cells.load_cell("bfs_1m_traversal_1chip").per_layer}
    assert {"scattered_accesses", "upload_bytes_per_call"} <= bfs
