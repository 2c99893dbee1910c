"""``reduce_1gib_percall_1chip`` (configuration ``shoc_reduction``, loop
``reduction``) held to what the other cells are held to, at 32 768 elements
and four work-groups on the CPU container (``JAX_PLATFORMS=cpu python3 -m
pytest benchmark/checks/test_reduction_cell.py -q``), and its readers held to
reductions made by hand.  Nothing here yields a device number.

- the sound program reads ``correct`` true through the loop, all three counts
  0 against limit 0, with exactly the cell's end-to-end metrics, one compute
  a call and four computes a call;
- each fault ``limits_why`` names reads ``correct`` false: a tree one halving
  short (by hand, and the program itself with the kernel so cut), a tile read
  before its stores, the previous call's partials, partials right in total and
  wrong by group, a dropped pass, a read-back that never came, a window of
  idle calls, the bfloat16 control; and, since the array is uploaded once and
  stays (ISSUE 49), a resident buffer lost between calls;
- the loop turns ``read`` off after the first upload and only where the
  configuration says so, and a run in which the array crossed inside the
  window reads ``upload_bytes_per_call.reduce`` above 0;
- ``kernel_cost`` and the readers on spans and operations made by hand;
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

CELL, CONFIG = "reduce_1gib_percall_1chip", "shoc_reduction"
NEW_METRICS = [
    "reduce_kernel_ms_per_call", "reduce_roofline",
    "group_barriers_per_launch", "local_row_accesses",
    "device_idle_share.reduce", "window_compiles.reduce",
    "xla_launch_share.reduce", "launch_ms_per_call.reduce",
    "loose_scalars_per_call.reduce", "dispatch_idle_ms_per_call.reduce",
    "unnamed_idle_share.reduce", "readback_ms_per_call.reduce"]
LATER_METRICS = [  # appended behind them: PR 46's two, PR 49's guard
    "reduce_gathered_accesses", "group_slice_accesses",
    "upload_bytes_per_call.reduce"]
GROUPS, LOCAL = 4, 256
GRID = 2 * LOCAL * GROUPS
SMALL_CFG = {"elements": 16 * GRID, "groups": GROUPS}
SMALL_TRAFFIC = {"n": GROUPS * LOCAL}
CYCLE = [16 * GRID - k * GRID for k in range(4)]
APART = 16 * GRID - 8 * GRID


def small_cell(**traffic) -> cells.Cell:
    cell = cells.load_cell(CELL)
    return cell._replace(cfg={**cell.cfg, **SMALL_CFG},
                         params={**cell.params, **SMALL_TRAFFIC, **traffic})


@pytest.fixture(scope="module")
def devices():
    from cekirdekler_tpu import hardware

    return hardware.chip_devices()  # the host CPU under JAX_PLATFORMS=cpu


def run_small(devices, seed=2**31 + 45, seconds=0.3, **traffic):
    compared = []
    result = run.run_cell(small_cell(**traffic), seed=seed, seconds=seconds,
                          trace=False, devices=devices, compared_out=compared)
    return result, compared


# -- the program through the loop, against the reference --------------------

def logged(monkeypatch) -> list:
    """``(the loop's log, calls of the window)`` as ``read_back`` leaves them."""
    logs = []
    real = run.read_back

    def read_back(ctx):
        out = real(ctx)
        logs.append((list(ctx.data["sums"]), len(ctx.walls)))
        return out

    monkeypatch.setattr(run, "read_back", read_back)
    return logs


def test_sound_program_is_exact_with_exactly_the_cells_metrics(
        devices, monkeypatch):
    logs = logged(monkeypatch)
    result, compared = run_small(devices)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3
    assert set(result["metrics"]) == {"call_p50_ms", "setup_s"}
    assert [(c.name, c.value, c.limit) for c in compared] == [
        ("partials_differing", 0.0, 0), ("sum_abs_err", 0.0, 0),
        ("partials_unwritten", 0.0, 0)]
    # warm-up takes the cycle through twice and ends on the one set apart; the
    # window goes on through the cycle; the fresh call is the one apart again
    (log, calls), = logs
    ns = [n for n, _sum in log]
    assert ns[:9] == CYCLE + CYCLE + [APART]
    assert ns[9:-1] == [CYCLE[k % 4] for k in range(calls)]
    assert ns[-1] == APART and len(log) == 9 + calls + 1
    assert len({s for _n, s in log}) == 5  # five prefixes, five sums


def test_four_computes_a_call_are_the_cycle_from_its_start(
        devices, monkeypatch):
    """The configuration's rule for a median that spreads too widely: a call
    becomes the cycle's four computes; the set apart stays one."""
    logs = logged(monkeypatch)
    result, compared = run_small(devices, iterations_per_call=4)
    assert result["correct"] is True and all(c.value == 0.0 for c in compared)
    (log, calls), = logs
    ns = [n for n, _sum in log]
    assert ns[:-1] == CYCLE * 8 + [APART] + CYCLE * calls
    assert ns[-1] == APART
    assert cells.load_module("loops", "reduction.py").items_per_call(
        small_cell(iterations_per_call=4).params) == 4 * GROUPS * LOCAL


# -- what must fail ---------------------------------------------------------

def sound(cell, data, n):
    return cell.ref.partials(data["g_idata"], n, GROUPS, LOCAL).astype(
        np.float32)


def lane_sums(data, n):
    """What every work item holds in its slot of the tile behind the walk."""
    return data["g_idata"][:n].reshape(-1, GROUPS, 2, LOCAL).sum(axis=(0, 2))


def compare(cell, data, window, fresh, log=None, **kw):
    """``window`` / ``fresh``: ``(n, partials)`` of the two calls compared;
    the loop's log says what it handed its caller (the sound sums unless
    ``log`` says otherwise)."""
    data["sums"] = log if log is not None else [
        (n, float(sound(cell, data, n).sum(dtype=np.float64)))
        for n in CYCLE + [window[0], fresh[0]]]
    observed = {"values": (window[0],), "outputs": {"g_odata": window[1]},
                "fresh": {"values": (fresh[0],),
                          "outputs": {"g_odata": fresh[1]}}}
    got = cell.ref.compare(cell.cfg, cell.params, data, (window[0],),
                           observed, 1, **kw)
    return {c.name: c for c in got}


def test_compare_passes_the_sound_calls_and_fails_each_named_fault():
    cell = small_cell()
    data, values = cell.ref.inputs(cell.cfg, cell.params,
                                   np.random.default_rng(3))
    assert values == (16 * GRID,) and data["g_odata"].tolist() == [-1.0] * 4
    assert set(np.unique(data["g_idata"])) == {0.0, 1.0, 2.0}
    n = CYCLE[2]
    window, fresh = (n, sound(cell, data, n)), (APART, sound(cell, data, APART))
    ok = compare(cell, data, window, fresh)
    assert all(c.ok and c.value == 0.0 for c in ok.values())
    tile = lane_sums(data, n)

    def fails(partials, total=None, by=("partials_differing", "sum_abs_err")):
        total = float(np.sum(partials, dtype=np.float64)
                      if total is None else total)
        log = [(m, float(sound(cell, data, m).sum(dtype=np.float64)))
               for m in CYCLE] + [(n, total), (APART, float(fresh[1].sum()))]
        got = compare(cell, data, (n, np.asarray(partials, np.float32)), fresh,
                      log=log)
        assert {k for k, c in got.items() if not c.ok} == set(by), got
        return got

    # a tree one halving short: work item 0 never adds work item 1's half
    got = fails(tile[:, 0::2].sum(axis=1))
    assert got["partials_differing"].value == 4.0
    # a tile read before its stores (the first barrier's job): the tree's
    # first pass reads the upper half as it was at the launch's start
    fails(tile[:, :LOCAL // 2].sum(axis=1))
    # the previous call's partials under this call's n
    fails(sound(cell, data, CYCLE[1]))
    # a dropped pass of the walk
    fails(cell.ref.partials(data["g_idata"], n - GRID, GROUPS, LOCAL))
    # right in total and wrong by group: the sum alone would pass
    got = fails(np.roll(window[1], 1), by=("partials_differing",))
    assert got["sum_abs_err"].value == 0.0
    # a read-back that never came: the poison is still there
    got = compare(cell, data, window, (APART, np.full(4, -1, np.float32)))
    assert got["partials_unwritten"].value == 4.0
    assert not got["partials_unwritten"].ok
    # a loop that handed its caller nothing has no number to show
    got = compare(cell, data, window, fresh, log=[])
    assert not got["sum_abs_err"].ok and np.isnan(got["sum_abs_err"].value)

    # the control stands in the program's place and reads not correct
    control = compare(cell, data, window, fresh, precision="bfloat16")
    assert control["partials_differing"].value == 8.0
    assert control["sum_abs_err"].value > 4 and not control["sum_abs_err"].ok
    with pytest.raises(ValueError):
        compare(cell, data, window, fresh, precision="float16")


def test_a_seeds_data_do_not_depend_on_the_hosts_cores(monkeypatch):
    """The array is drawn in fixed pieces by a few threads (set-up time at
    1 GiB): the same seed gives the same array whatever the threads."""
    cell = small_cell()
    ref = cell.ref

    def data(seed):
        return ref.inputs(cell.cfg, cell.params,
                          np.random.default_rng(seed))[0]["g_idata"]

    many = data(2**31 + 7)
    monkeypatch.setattr(ref.os, "cpu_count", lambda: 1)
    np.testing.assert_array_equal(data(2**31 + 7), many)
    assert many.dtype == np.float32 and many.size == SMALL_CFG["elements"]
    assert (data(2**31 + 8) != many).mean() > 0.5
    # every piece has all three values: none was left unfilled
    pieces = np.array_split(many, ref.PIECES)
    assert all(set(np.unique(p)) == {0.0, 1.0, 2.0} for p in pieces)


def test_the_control_is_the_kernel_with_a_bfloat16_tile():
    """Exact where bfloat16 holds the sums (a work item's few small terms),
    stuck where it cannot (a long walk)."""
    cell = small_cell()
    x = np.ones(16 * GRID, np.float32)
    few = cell.ref.partials_bfloat16(x, 2 * GRID, GROUPS, LOCAL)
    np.testing.assert_array_equal(few, cell.ref.partials(x, 2 * GRID, GROUPS,
                                                         LOCAL))
    long = np.ones(2048 * 2 * LOCAL, np.float32)
    got = cell.ref.partials_bfloat16(long, long.size, 1, LOCAL)
    assert got[0] < cell.ref.partials(long, long.size, 1, LOCAL)[0] / 4


def test_the_program_with_its_tree_one_halving_short_is_not_correct(
        devices, monkeypatch):
    real = cells.kernel_source
    monkeypatch.setattr(cells, "kernel_source", lambda cfg: real(cfg).replace(
        "s > 0; s >>= 1", "s > 1; s >>= 1"))
    result, compared = run_small(devices)
    assert result["correct"] is False
    by = {c.name: c for c in compared}
    assert by["partials_differing"].value == 8.0 and by["sum_abs_err"].value > 0
    assert by["partials_unwritten"].ok


def test_a_window_of_idle_calls_is_not_correct(devices, monkeypatch):
    """Warm-up's last call left the partials of the prefix set apart; a window
    whose calls compute nothing hands its caller that sum under another n."""
    real_window = run.window

    def idle_window(ctx, seconds, compiles):
        call, log = ctx.call, ctx.data["sums"]
        partials = ctx.arrays["g_odata"].host()
        ctx.call = lambda: log.append(
            (int(ctx.values[0]), float(np.sum(partials, dtype=np.float64))))
        try:
            real_window(ctx, seconds, compiles)
        finally:
            ctx.call = call

    monkeypatch.setattr(run, "window", idle_window)
    result, compared = run_small(devices)
    assert result["correct"] is False
    by = {c.name: c for c in compared}
    assert by["partials_differing"].value >= 3 and by["sum_abs_err"].value > 0


def span(kind, start, ms, lane=0, **stats) -> host_phases.HostSpan:
    return host_phases.HostSpan(
        kind, start, start + 1e-3 * ms, 1,
        {"lane": lane, **stats} if kind.startswith("ck/") else {})


# -- the array is uploaded once and stays (ISSUE 49) --------------------------

def without_residency(cell: cells.Cell) -> cells.Cell:
    """The configuration as it stood until PR 49: no ``after_first_upload``."""
    arrays = [{k: v for k, v in spec.items() if k != "after_first_upload"}
              for spec in cell.cfg["arrays"]]
    return cell._replace(cfg={**cell.cfg, "arrays": arrays})


def test_a_resident_buffer_lost_between_calls_is_not_correct(
        devices, monkeypatch):
    """The caller says it has not changed the array and the lane is to keep
    what it holds: a lane that lost the buffer makes a new one of zeros
    (``ensure_resident``), and every partial reads 0."""
    from cekirdekler_tpu.core.worker import Worker

    real_window, real_ensure = run.window, Worker.ensure_resident

    def forgetful(self, arr):
        if arr.name == "g_idata":
            self._buffers.pop(id(arr), None)
        return real_ensure(self, arr)

    def window(ctx, seconds, compiles):
        monkeypatch.setattr(Worker, "ensure_resident", forgetful)
        try:
            real_window(ctx, seconds, compiles)
        finally:
            monkeypatch.setattr(Worker, "ensure_resident", real_ensure)

    monkeypatch.setattr(run, "window", window)
    logs = logged(monkeypatch)
    result, compared = run_small(devices)
    assert result["correct"] is False
    by = {c.name: c for c in compared}
    assert by["partials_differing"].value >= GROUPS
    assert by["sum_abs_err"].value > 0 and by["partials_unwritten"].ok
    (log, calls), = logs
    assert [s for _n, s in log[9:9 + calls]] == [0.0] * calls


def spans_of_a_run(devices, monkeypatch, cell):
    """The spans the upload reader goes by, of one small run on the CPU:
    ``bench/call`` from the harness's own span sites, ``ck/upload`` (with the
    bytes the program's span carries) and ``ck/launch`` around the lane's two
    methods.  ``(lines, window start, window end, result, reads)``: ``reads`` is
    ``g_idata.read`` as every upload or launch found it."""
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
                reads.append((kind, arrays["g_idata"].read))
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
        arrays.update(ctx.arrays)
        return ctx

    monkeypatch.setattr(run, "build", build)
    result = run.run_cell(cell, seed=2**31 + 49, seconds=0.2, trace=False,
                          devices=devices)
    return ([caller, lane], caller[0].start, caller[-1].end, result, reads)


def upload_bytes_per_call(lines, t0, t1):
    r = cells.load_reader("levels_per_call").reduce(lines, t0, t1, 0)
    return cells.load_reader("upload_bytes_per_call.reduce").read(
        SimpleNamespace(traversals=r))


def test_the_loop_turns_read_off_after_the_first_upload_and_nothing_crosses(
        devices, monkeypatch):
    cell = small_cell()
    (spec,) = [s for s in cell.cfg["arrays"] if "after_first_upload" in s]
    assert spec["name"] == "g_idata" and spec["after_first_upload"] == {
        "read": False}
    lines, t0, t1, result, reads = spans_of_a_run(devices, monkeypatch, cell)
    assert result["correct"] is True
    # the harness's one synchronous compute finds the flag on and uploads the
    # array whole, once in the process; every later launch finds it off
    assert reads[:2] == [("ck/upload", True), ("ck/launch", True)]
    assert all(kind == "ck/launch" and read is False
               for kind, read in reads[2:]) and len(reads) > 12
    (upload,) = [s for s in lines[1] if s.name == "ck/upload"]
    assert upload.stats["tag"] == "g_idata" and upload.end <= t0
    assert upload.stats["bytes"] == 4 * SMALL_CFG["elements"]
    assert upload_bytes_per_call(lines, t0, t1) == 0.0


def test_an_array_that_crossed_inside_the_window_reads_its_bytes(
        devices, monkeypatch):
    """Only where the configuration says so: without the entry the loop
    leaves ``read`` on, the program uploads the array at every synchronous
    compute (the cell until PR 49), and the guard reads the bytes."""
    cell = without_residency(small_cell())
    lines, t0, t1, result, reads = spans_of_a_run(devices, monkeypatch, cell)
    assert result["correct"] is True  # the same sums, by the dearer road
    assert all(read is True for _kind, read in reads)
    assert upload_bytes_per_call(lines, t0, t1) == 4.0 * SMALL_CFG["elements"]
    # one upload a call would already trip it: the reader by hand
    calls = [span("bench/call", 10.0, 40), span("bench/call", 10.1, 40)]
    lane = [span("ck/launch", 10.01, 1), span("ck/launch", 10.11, 1),
            span("ck/upload", 10.105, 1, tag="g_idata", bytes=2**30)]
    assert upload_bytes_per_call([calls, lane], 10.0, 10.2) == 2.0**29
    assert upload_bytes_per_call([calls, lane[:2]], 10.0, 10.2) == 0.0
    # a program without the spans leaves nothing to read, not 0
    assert cells.load_reader("upload_bytes_per_call.reduce").read(
        SimpleNamespace(traversals=None)) is None


# -- kernel_cost and the readers against reductions made by hand -------------

def test_kernel_cost_is_the_least_traffic_of_the_work():
    cell = cells.load_cell(CELL)
    elements = cell.cfg["elements"]
    cost = cell.ref.kernel_cost(cell.cfg, cell.params, 16384)
    assert cost == {"ops": elements, "bytes": 4 * elements + 256}
    assert cell.ref.kernel_cost(cell.cfg, cell.params, 16384, n=1000) == {
        "ops": 1000, "bytes": 4256}
    # 1.074 GB at the chip's 819 GB/s: 1.31 ms (ISSUE 45's reckoning at 2^28)
    peak = cells.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    big = cell.ref.kernel_cost(cell.cfg, cell.params, 16384, n=2**28)["bytes"]
    assert 1e3 * big / peak == pytest.approx(1.311, abs=1e-3)



LOCAL_FIELD = "arrays:1;bytes:1024;barriers:2;sites:shift:6,uniform:1,row:0"


def lines_by_hand(field=LOCAL_FIELD):
    launch = "ck/launch"
    return [[span("bench/call", 10.0, 1000), span("bench/call", 12.0, 1000)],
            [span(launch, 9.6, 5, local="arrays:9;barriers:9;sites:row:9"),
             span(launch, 10.5, 5, lane=1, local="barriers:7"),  # another lane
             span(launch, 10.6, 5),                              # no field
             span(launch, 12.1, 5, local=field),
             span(launch, 12.6, 5, local="barriers:5")]]


def test_the_local_field_by_hand():
    reader = cells.load_reader("group_barriers_per_launch")
    want = {"arrays": 1, "bytes": 1024, "barriers": 2, "shift": 6,
            "uniform": 1, "row": 0}
    assert reader.parse(LOCAL_FIELD) == want
    # a profiler annotation carries the commas as ``;``
    assert reader.parse(LOCAL_FIELD.replace(",", ";")) == want
    assert reader.local_field(lines_by_hand(), 10.0, 14.0, 0) == want
    assert reader.local_field(lines_by_hand(), 10.0, 14.0, 2) is None
    assert reader.local_field(lines_by_hand(), 10.0, 12.0, 0) is None


OPS = {("fusion.9", "fusion"): 0.5, ("gather_fusion", "fusion"): 1.25,
       ("scatter.3", "scatter"): 0.2, ("copy.3", "copy"): 0.05,
       ("while.1", "while"): 1.9}


def by_hand(field=LOCAL_FIELD) -> SimpleNamespace:
    cell = small_cell()
    data, _ = cell.ref.inputs(cell.cfg, cell.params, np.random.default_rng(3))
    # the log as a run leaves it: warm-up, the window's four calls, the fresh
    data["sums"] = [(APART, 0.0)] + [(n, 0.0) for n in CYCLE] + [(APART, 0.0)]
    reduced = xplane.Reduced(
        t0=10.0, t1=14.0, busy_s={0: 3.0}, op_seconds={0: dict(OPS)},
        op_counts={0: {k: 4 for k in OPS}}, idle_by_span={0: {}}, calls=4)
    reader = cells.load_reader("group_barriers_per_launch")
    return SimpleNamespace(
        cell=cell, cfg=cell.cfg, params=cell.params, data=data,
        n=int(cell.params["n"]), reduced=reduced, window_compiles=0,
        local_field=reader.local_field(lines_by_hand(field), 10.0, 14.0, 0),
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def read(metric: str, ctx):
    return cells.load_reader(metric).read(ctx)


def test_the_readers_by_hand():
    ctx = by_hand()
    assert read("group_barriers_per_launch", ctx) == 2.0
    assert read("local_row_accesses", ctx) == 0.0
    assert read("local_row_accesses", by_hand("sites:shift:1,row:3")) == 3.0
    # the containers left out, the copy counted: 2.0 s over four computes
    assert read("reduce_kernel_ms_per_call", ctx) == pytest.approx(500.0)
    assert read("window_compiles.reduce", ctx) == 0.0
    assert read("device_idle_share.reduce", ctx) == pytest.approx(25.0)
    # each compute's own n: four prefixes, the partials' 16 bytes each
    want = sum(4 * n + 4 * GROUPS for n in CYCLE)
    assert cells.load_reader("reduce_roofline").least_bytes(ctx, 4) == want
    assert read("reduce_roofline", ctx) == pytest.approx(
        100.0 * want / 819e9 / 2.0)
    assert 0 < read("reduce_roofline", ctx) < 100


def test_readers_leave_the_metric_out_where_nothing_is_to_read():
    """A program without the field (a parent commit), a window without
    operations: None, before any trace is looked for."""
    ctx = by_hand()
    ctx.local_field = None
    assert read("group_barriers_per_launch", ctx) is None
    assert read("local_row_accesses", ctx) is None
    ctx = by_hand("arrays:0;bytes:0")  # a field that names neither
    assert read("group_barriers_per_launch", ctx) is None
    assert read("local_row_accesses", ctx) is None
    ctx = by_hand()
    ctx.reduced = ctx.reduced._replace(op_seconds={0: {}})
    assert read("reduce_kernel_ms_per_call", ctx) is None
    assert read("reduce_roofline", ctx) is None


# -- the manifest, by name --------------------------------------------------

def test_the_configuration_the_cell_and_its_metrics_are_in_the_manifest():
    man = cells.manifest()
    row = next(w for w in man["workloads"] if w["name"] == CELL)
    assert row == {**row, "config": CONFIG, "traffic": CELL, "chips": 1}
    conf = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == [] and conf["file"] == (
        f"benchmark/configs/{CONFIG}.json")
    assert all(len(s) <= 200 for s in (row["why"], conf["why"],
                                       conf["source"]))
    listed = {m["name"]: m for m in man["per_layer"]}
    assert all(listed[m]["workloads"] == [CELL]
               and listed[m]["moves"] == "call_p50_ms" for m in NEW_METRICS)
    assert listed["reduce_roofline"]["unit"] == "%"
    assert {listed[m]["source"] for m in NEW_METRICS[:2]} == {"device_trace"}
    cell = cells.load_cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == ["call_p50_ms", "setup_s"]
    # by name: a later PR appends behind them (PR 46 did, PR 49 did)
    mine = [m["name"] for m in cell.per_layer]
    assert set(NEW_METRICS + LATER_METRICS) <= set(mine)
    assert len(mine) == len(set(mine))
    guard = listed["upload_bytes_per_call.reduce"]
    assert guard == {
        "name": "upload_bytes_per_call.reduce", "unit": "bytes",
        "better": "lower", "source": "program_span", "layer": "transfers",
        "moves": "call_p50_ms", "workloads": [CELL]}
    assert listed["upload_bytes_per_call"]["workloads"] == [
        "bfs_1m_traversal_1chip"]
    assert cell.cfg["source"] == conf["source"]
    assert cell.cfg["reduced"] == [] and cell.cfg["lanes"] == 1
    assert cell.cfg["elements"] == 2**28  # the name's 1 GiB (ISSUE 49)
    assert "elements" in cell.cfg["assumed"]
    assert "2^28" in cell.cfg["deployment"] and "1 GiB" in row["why"]
    # uploaded once and stays: the configuration states it, the loop applies it
    assert [(s["name"], s.get("after_first_upload")) for s in cell.cfg[
        "arrays"]] == [("g_idata", {"read": False}), ("g_odata", None)]
    assert cell.cfg["fresh_call"]["upload"] is False
    assert cell.params["sync"] == "call"
    assert cell.params["n"] == 16384 == cell.cfg["groups"] * cell.cfg[
        "local_range"] and cell.params["loop"] == "reduction"
    assert cell.params["iterations_per_call"] in (1, 4)
    assert cell.params["warmup_calls"] == 8 and cell.params["pins"] == {}
    # 8192 passes of six operations a call: 3 s of trace are read in ~2 min
    assert cell.params["trace_seconds"] == 3
    plan = cell.ref.call_values(cell.cfg, cell.params, (cell.cfg["elements"],))
    assert len(plan["cycle"]) == 4 and plan["apart"] not in plan["cycle"]
    assert all(n % 32768 == 0 for (n,) in plan["cycle"] + [plan["apart"]])
    for m in NEW_METRICS:
        assert cells.load_reader(m) is not None
    e2e = next(m for m in man["end_to_end"] if m["name"] == "call_p50_ms")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.01
    assert not any(CELL in m.get("workloads", ()) for m in man["end_to_end"]
                   if m["name"] not in ("call_p50_ms", "setup_s"))
    # the kernel is the source's, with its tile, its tree and its barriers
    text = cells.kernel_source(cell.cfg)
    assert text.count("__kernel void") == 1 and "reduce" in text
    assert "__local float sdata[256];" in text
    assert text.count("barrier(CLK_LOCAL_MEM_FENCE);") == 2
    assert "g_odata[get_group_id(0)] = sdata[0];" in text
    # the reference is plain numpy: it imports nothing of the program
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           cell.cfg["reference"]), encoding="utf-8") as f:
        assert "cekirdekler" not in f.read()


def test_the_accepted_cells_report_what_they_reported():
    """By name, whatever this PR appended behind them."""
    percall = cells.load_cell("mandelbrot_percall_1chip")
    assert [m["name"] for m in percall.end_to_end] == ["call_p50_ms.percall",
                                                       "setup_s"]
    assert "launch_ms_per_call" in [m["name"] for m in percall.per_layer]
    assert not [m for m in percall.per_layer if m["name"] in NEW_METRICS]
    for name in ("mvt_16k_window", "bfs_1m_traversal_1chip",
                 "nbody_8k_window"):
        cell = cells.load_cell(name)
        assert not [m for m in cell.per_layer if m["name"] in NEW_METRICS]
