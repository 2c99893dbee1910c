"""``bfs_1m_traversal_1chip`` (configuration ``rodinia_bfs``, loop
``traversal``) held to what the other cells are held to, at 4 000 nodes on
the CPU container (``JAX_PLATFORMS=cpu python3 -m pytest
benchmark/checks/test_bfs_cell.py -q``), and its readers held to reductions
made by hand.  Nothing here yields a device number.

- the sound program reads ``correct`` true through the loop, both numbers 0
  against limit 0, with exactly the cell's end-to-end metrics;
- the plain reference agrees with the textbook queue on a small graph;
- each of the four faults ``limits_why`` names reads ``correct`` false: a
  traversal cut one level short, a stale ``cost``, a dropped or a doubled
  scatter, a flag read one level late;
- ``kernel_cost`` on a level made by hand;
- the configuration, the cell and every new entry are in the manifest, found
  BY NAME (a later PR appends behind them).

The spans by hand: lane 0, window 10-14 s.  Call A (10.0-11.0) is two levels
and the read-back: uploads of mask (100 bytes), cost (400) and over (1) and a
launch at 10.1, over down 10.20-10.25; over up 10.30-10.31, launch, over down
10.40-10.46; cost down 10.9-10.95.  Call B (12.0-13.0) is three levels, each
1 byte up (1 ms) and down (20 ms), and uploads 500 bytes of state.  A call
that starts before the window, and lane 1's spans, are another's.  So: 2
calls, 5 levels = 2.5 a call; bytes up (501 + 1 + 503) / 2 = 502.5 a call;
the flag's spans (10 + 50 + 10 + 60 + 3 + 60) ms / 5 = 38.6 ms a level.

The device by hand (``OPS``): a scatter 0.50 s, a custom (gather) fusion
0.25 s, a loop fusion 0.20 s, a copy 0.05 s, a ``while`` container
0.95 s left out: 1.00 s over 5 levels = 200 ms a level.
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

CELL, CONFIG = "bfs_1m_traversal_1chip", "rodinia_bfs"
NEW_METRICS = [
    "bfs_kernel_ms_per_level", "bfs_roofline", "bfs_scatter_share",
    "bfs_gather_share", "scattered_accesses", "levels_per_call",
    "flag_roundtrip_ms_per_level", "upload_bytes_per_call",
    "device_idle_share.bfs", "window_compiles.bfs", "xla_launch_share.bfs",
    "launch_ms_per_call.bfs", "loose_scalars_per_call.bfs",
    "dispatch_idle_ms_per_call.bfs", "resync_idle_ms_per_call.bfs",
    "unnamed_idle_share.bfs"]
SMALL_CFG = {"nodes": 4000, "sources": [0, 1, 2], "source_apart": 3}
SMALL_TRAFFIC = {"n": 4096}


def small_cell() -> cells.Cell:
    cell = cells.load_cell(CELL)
    return cell._replace(cfg={**cell.cfg, **SMALL_CFG},
                         params={**cell.params, **SMALL_TRAFFIC})


@pytest.fixture(scope="module")
def devices():
    from cekirdekler_tpu import hardware

    return hardware.chip_devices()  # the host CPU under JAX_PLATFORMS=cpu


def run_small(devices, seed=2**31 + 5, seconds=0.3):
    compared = []
    result = run.run_cell(small_cell(), seed=seed, seconds=seconds,
                          trace=False, devices=devices,
                          compared_out=compared)
    return result, compared


# -- the program through the loop, against the reference --------------------

def test_sound_program_is_exact_with_exactly_the_cells_metrics(devices):
    result, compared = run_small(devices)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3
    assert set(result["metrics"]) == {"call_p50_ms", "setup_s"}
    assert [(c.name, c.value, c.limit) for c in compared] == [
        ("cost_differing", 0.0, 0), ("levels_off", 0.0, 0)]


def test_the_loop_cycles_the_sources_and_sets_one_apart(devices, monkeypatch):
    """Warm-up takes every source through twice and ends on the one set
    apart; the window goes on through the cycle; the fresh call is the one
    apart again.  The log the comparison reads says so."""
    logs = []
    real = run.read_back

    def read_back(ctx):
        out = real(ctx)
        logs.append((list(ctx.data["traversals"]), len(ctx.walls)))
        return out

    monkeypatch.setattr(run, "read_back", read_back)
    result, _ = run_small(devices)
    assert result["correct"] is True
    (log, calls), = logs
    sources = [s for s, _levels in log]
    assert sources[:7] == [0, 1, 2, 0, 1, 2, 3]
    assert sources[7:-1] == [(0, 1, 2)[k % 3] for k in range(calls)]
    assert sources[-1] == 3 and len(log) == 7 + calls + 1
    assert all(levels >= 2 for _s, levels in log)


def test_the_reference_is_the_textbook_queue_on_a_small_graph():
    cell = small_cell()
    data, values = cell.ref.inputs(cell.cfg, cell.params,
                                   np.random.default_rng(7))
    assert values == (4000,) and data["over"].shape == (1,)
    csr = data["starting"], data["no_of_edges"], data["edges"]
    assert int(data["no_of_edges"][4000:].sum()) == 0  # the range's padding
    for source in (0, 17, 3999):
        stats = []
        cost, levels = cell.ref.bfs(*csr, source, stats)
        np.testing.assert_array_equal(cost, cell.ref.bfs_queue(*csr, source))
        assert levels == int(cost.max()) + 1 == len(stats)
        assert stats[0][0] == 1 and stats[-1][2] == 0
        # every node but the source is discovered once
        assert sum(found for _f, _e, found in stats) == int(
            (cost > 0).sum()) and (cost[4000:] == -1).all()
    # another seed relabels the same structure: same degrees, same levels
    other, _ = cell.ref.inputs(cell.cfg, cell.params,
                               np.random.default_rng(8))
    assert sorted(other["no_of_edges"]) == sorted(data["no_of_edges"])
    assert not np.array_equal(other["edges"], data["edges"])
    # ... within work-groups: a permutation that keeps every node among its
    # 256 neighbours, and so in its chunk of the launch ladder
    for labels in (data["relabel"], other["relabel"]):
        assert np.array_equal(np.sort(labels), np.arange(4000))
        assert np.array_equal(labels // 256, np.arange(4000) // 256)
    assert not np.array_equal(data["relabel"], other["relabel"])
    assert cell.cfg["seed_relabels"] == "within_work_groups"
    everywhere, _ = cell.ref.inputs({**cell.cfg, "seed_relabels": True},
                                    cell.params, np.random.default_rng(7))
    assert not np.array_equal(everywhere["relabel"] // 256,
                              np.arange(4000) // 256)
    identity, _ = cell.ref.inputs({**cell.cfg, "seed_relabels": False},
                                  cell.params, np.random.default_rng(7))
    assert np.array_equal(identity["relabel"], np.arange(4000))
    a = cell.ref.bfs(*csr, int(data["relabel"][0]))
    b = cell.ref.bfs(other["starting"], other["no_of_edges"], other["edges"],
                     int(other["relabel"][0]))
    assert a[1] == b[1] and np.array_equal(np.bincount(a[0] + 1),
                                           np.bincount(b[0] + 1))


# -- what must fail ---------------------------------------------------------

def observed_of(cell, data, window, fresh):
    """What ``run.read_back`` hands the comparison: ``cost`` after the
    window's last call and after the fresh call, the log's last two entries
    naming their sources and levels."""
    data["traversals"] = [(0, 99), window[0], fresh[0]]
    return {"outputs": {"cost": window[1]},
            "fresh": {"outputs": {"cost": fresh[1]}}}


def sound(cell, data, base):
    cost, levels = cell.ref.bfs(
        data["starting"], data["no_of_edges"], data["edges"],
        int(data["relabel"][base]))
    return (base, levels), cost


def compare(cell, data, window, fresh, **kw):
    got = cell.ref.compare(cell.cfg, cell.params, data, (4000,),
                           observed_of(cell, data, window, fresh), 1, **kw)
    return {c.name: c for c in got}


def test_compare_passes_the_sound_calls_and_fails_each_named_fault():
    cell = small_cell()
    data, _ = cell.ref.inputs(cell.cfg, cell.params, np.random.default_rng(3))
    window, fresh = sound(cell, data, 2), sound(cell, data, 3)
    ok = compare(cell, data, window, fresh)
    assert all(c.ok and c.value == 0.0 for c in ok.values())
    (base, levels), cost = window
    last = int(cost.max())

    # a traversal cut one level short: the last level's nodes were never
    # discovered and the host stopped a level early
    short = np.where(cost == last, -1, cost)
    got = compare(cell, data, ((base, levels - 1), short), fresh)
    assert got["cost_differing"].value == float((cost == last).sum()) > 0
    assert got["levels_off"].value == 1.0 and not got["levels_off"].ok

    # a stale cost: the previous source's distances under this call's name
    stale = sound(cell, data, 1)[1]
    got = compare(cell, data, ((base, levels), stale), fresh)
    assert got["cost_differing"].value > 2000 and got["levels_off"].ok

    # a dropped scatter: one discovered node keeps -1; a doubled one: + 2
    for fault in (-1, None):
        bad = cost.copy()
        node = int(np.flatnonzero(cost == 3)[0])
        bad[node] = fault if fault is not None else cost[node] + 1
        got = compare(cell, data, ((base, levels), bad), fresh)
        assert got["cost_differing"].value == 1.0
        assert not got["cost_differing"].ok and got["levels_off"].ok

    # a flag read one level late: one compute too many, cost exact
    got = compare(cell, data, ((base, levels + 1), cost), fresh)
    assert got["cost_differing"].ok and got["levels_off"].value == 1.0

    # the fresh call is held to the same: its poison must be gone
    poisoned = np.full_like(cost, -7)
    got = compare(cell, data, window, (fresh[0], poisoned))
    assert got["cost_differing"].value == float(cost.size)

    # the control stands in the program's place and reads not correct
    control = compare(cell, data, window, fresh, precision="one-level-short")
    assert control["cost_differing"].value > 0
    assert control["levels_off"].value == 2.0
    with pytest.raises(ValueError):
        compare(cell, data, window, fresh, precision="bfloat16")


def test_a_window_of_idle_calls_is_not_correct(devices, monkeypatch):
    """Warm-up's last call left the distances from the source set apart; a
    window whose calls do nothing leaves them there under another name."""
    real_window = run.window

    def idle_window(ctx, seconds, compiles):
        call, log = ctx.call, ctx.data["traversals"]
        ctx.call = lambda: log.append((ctx.cfg["sources"][0], log[-1][1]))
        try:
            real_window(ctx, seconds, compiles)
        finally:
            ctx.call = call

    monkeypatch.setattr(run, "window", idle_window)
    result, compared = run_small(devices)
    assert result["correct"] is False
    assert compared[0].name == "cost_differing" and compared[0].value > 1000


def test_kernel_cost_is_the_algorithms_bytes_of_a_level_by_hand():
    """1 000 work-items, a frontier of 10 nodes with 60 edge entries that
    discovers 25: both masks once a node (2 000); starting, no_of_edges,
    cost and the cleared mask byte a frontier node (13 x 10); the entry and
    a visited byte an edge (5 x 60); cost and updating a discovered node
    and what BFS_2 stores for it (8 x 25)."""
    cell = cells.load_cell(CELL)
    cost = cell.ref.kernel_cost(cell.cfg, cell.params, 1000, 10, 60, 25)
    assert cost == {"ops": 0, "bytes": 2000 + 130 + 300 + 200}
    assert cell.ref.kernel_cost(cell.cfg, cell.params, 1000) == {
        "ops": 0, "bytes": 2000}


# -- the readers against reductions made by hand ----------------------------

def span(kind, start, ms, lane=0, **stats) -> host_phases.HostSpan:
    return host_phases.HostSpan(
        kind, start, start + 1e-3 * ms, 1,
        {"lane": lane, **stats} if kind.startswith("ck/") else {})


ACCESS = "slice:8;strided:0;uniform:1;gather:2;scatter:2;carried:1"


def lines_by_hand():
    up, down, launch = "ck/upload", "ck/download", "ck/launch"
    caller = [
        span("bench/call", 9.5, 600),                       # before
        span("bench/call", 10.0, 1000), span("bench/call", 12.0, 1000)]
    lane = [
        span(launch, 9.6, 5, access=ACCESS),
        # call A
        span(up, 10.01, 2, tag="mask", bytes=100),
        span(up, 10.02, 2, tag="cost", bytes=400),
        span(up, 10.03, 10, tag="over", bytes=1),
        span(launch, 10.1, 5, access=ACCESS),
        span(down, 10.20, 50, tag="over", bytes=1),
        span(down, 10.21, 0, tag="part:landed", name="over", bytes=1),
        span(up, 10.30, 10, tag="over", bytes=1),
        span(launch, 10.32, 5, access=ACCESS),
        span(down, 10.40, 60, tag="over", bytes=1),
        span(down, 10.90, 50, tag="cost", bytes=400),
        span(launch, 10.5, 5, lane=1, access="scatter:9"),  # another lane
        # call B
        span(up, 12.01, 2, tag="cost", bytes=500),
        *[s for k in range(3) for s in (
            span(up, 12.1 + 0.2 * k, 1, tag="over", bytes=1),
            span(launch, 12.12 + 0.2 * k, 5),
            span(down, 12.15 + 0.2 * k, 20, tag="over", bytes=1))]]
    return [caller, lane]


def test_the_traversal_reduction_by_hand():
    reader = cells.load_reader("levels_per_call")
    r = reader.reduce(lines_by_hand(), 10.0, 14.0, 0)
    assert (r.calls, r.levels, r.flag_moves) == (2, 5, 10)
    assert r.upload_bytes == 501 + 1 + 503
    assert r.flag_up_s == pytest.approx(0.023)
    assert r.flag_down_s == pytest.approx(0.170)
    assert r.access == ACCESS
    # a window that holds no whole call, a lane that launched nothing
    assert reader.reduce(lines_by_hand(), 10.5, 11.5, 0) is None
    assert reader.reduce(lines_by_hand(), 10.0, 14.0, 2) is None


OPS = {("scatter_fusion.3", "fusion"): 0.50, ("fusion.7", "fusion"): 0.25,
       ("add_fusion", "fusion"): 0.20, ("copy.3", "copy"): 0.05,
       ("while.1", "while"): 0.95}


def by_hand() -> SimpleNamespace:
    cell = small_cell()
    data, _ = cell.ref.inputs(cell.cfg, cell.params, np.random.default_rng(3))
    # the log as a run leaves it: warm-up, the window's two calls, the fresh
    data["traversals"] = [(0, 9), (3, 9), (1, 9), (2, 9), (3, 9)]
    reduced = xplane.Reduced(
        t0=10.0, t1=14.0, busy_s={0: 3.0}, op_seconds={0: dict(OPS)},
        op_counts={0: {k: 4 for k in OPS}}, idle_by_span={0: {}}, calls=2)
    reader = cells.load_reader("levels_per_call")
    return SimpleNamespace(
        cell=cell, cfg=cell.cfg, params=cell.params, data=data,
        n=int(cell.params["n"]), reduced=reduced, window_compiles=0,
        traversals=reader.reduce(lines_by_hand(), 10.0, 14.0, 0),
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def read(metric: str, ctx):
    return cells.load_reader(metric).read(ctx)


def test_the_span_readers_by_hand():
    ctx = by_hand()
    assert read("levels_per_call", ctx) == 2.5
    assert read("upload_bytes_per_call", ctx) == 502.5
    assert read("flag_roundtrip_ms_per_level", ctx) == pytest.approx(38.6)
    assert read("scattered_accesses", ctx) == 2.0
    assert read("bfs_kernel_ms_per_level", ctx) == pytest.approx(200.0)
    assert read("window_compiles.bfs", ctx) == 0.0
    assert read("device_idle_share.bfs", ctx) == pytest.approx(25.0)


def test_the_roofline_is_the_levels_bytes_over_bandwidth_over_their_time():
    ctx = by_hand()
    cell, data = ctx.cell, ctx.data
    want = 0
    for base in (1, 2):  # the window's two calls, by the log
        stats = []
        cell.ref.bfs(data["starting"], data["no_of_edges"], data["edges"],
                     int(data["relabel"][base]), stats)
        want += sum(2 * 4096 + 13 * f + 5 * e + 8 * d for f, e, d in stats)
    assert cells.load_reader("bfs_roofline").least_bytes(ctx, 2) == want
    assert read("bfs_roofline", ctx) == pytest.approx(
        100.0 * want / 819e9 / 1.0)
    assert 0 < read("bfs_roofline", ctx) < 100


# read off the cell's trace on the chip (PR 40)
SCATTER_FUSION = (
    "%fusion.26 = s8[1000192]{0:T(1024)(128)(4,1)} fusion(s8[1000192]{0:T(1024)"
    "(128)(4,1)} %get-tuple-element.548, s32[524288]{0:T(1024)S(1)} "
    "%get-tuple-element.505, s8[524288]{0:T(1024)(128)(4,1)S(1)} "
    "%get-tuple-element.506), kind=kCustom, calls=%fused_computation.3.clone")
GATHER_FUSION = (
    "%fusion.24 = s8[524288]{0:T(1024)(128)(4,1)S(1)} fusion(s8[1000192]{0:"
    "T(1024)(128)(4,1)} %get-tuple-element.563, s32[524288]{0:T(1024)S(1)} "
    "%broadcast_clamp_fusion.2), kind=kCustom, calls=%fused_computation.1")
ROW_GATHER = (
    "%fusion.23 = s32[262144,128]{1,0:T(8,128)} fusion(s32[93758,128]{1,0:"
    "T(8,128)} %get-tuple-element.466, s32[262144]{0:T(1024)S(1)} %fusion.22), "
    "kind=kCustom, calls=%fused_computation.1.clone.clone")
INDEX_SORT = (
    "%sort.2 = (s32[524288]{0:T(1024)S(1)}, s8[524288]{0:T(1024)(128)(4,1)S(1)})"
    " sort(s32[524288]{0:T(1024)S(1)} %get-tuple-element.504, s8[524288]{0:"
    "T(1024)(128)(4,1)S(1)} %broadcast.142), dimensions={0}, to_apply=%compare")
LOOP_FUSION = ("%add_fusion = s32[524288]{0:T(1024)} fusion(s32[524288]{0} "
               "%p), kind=kLoop, calls=%fused_computation.5")
BARE_SCATTER = ("%scatter.2 = s32[16384]{0} scatter(s32[16384]{0} %p, "
                "s32[16384,1]{1,0} %i, s32[16384]{0} %u), to_apply=%assign")


def test_the_shares_tell_scatters_from_gathers(monkeypatch):
    reader = cells.load_reader("bfs_scatter_share")
    assert all(reader.is_scatter(t) and not reader.is_gather(t)
               for t in (SCATTER_FUSION, INDEX_SORT, BARE_SCATTER))
    assert all(reader.is_gather(t) and not reader.is_scatter(t)
               for t in (GATHER_FUSION, ROW_GATHER))
    assert not reader.is_gather(LOOP_FUSION) and not reader.is_scatter(
        LOOP_FUSION)
    events = [(SCATTER_FUSION, 9.0, 9.9),                  # before
              (SCATTER_FUSION, 10.0, 10.4), (GATHER_FUSION, 10.4, 10.65),
              (LOOP_FUSION, 10.65, 10.85), (BARE_SCATTER, 13.9, 14.5)]
    # a profile by hand: one chip's ``XLA Ops`` line (every reader loads
    # its modules anew, so the trace is what is patched, not a function)
    line = SimpleNamespace(name=xplane.OPS_LINE, events=[
        SimpleNamespace(name=text, start_ns=a * 1e9, duration_ns=(b - a) * 1e9)
        for text, a, b in events])
    profile = SimpleNamespace(planes=[
        SimpleNamespace(name="/device:TPU:0", lines=[line])])
    monkeypatch.setattr(xplane, "_profile", lambda path: profile)
    monkeypatch.setattr(xplane, "find_xplane", lambda _dir: "by hand")
    ctx = by_hand()
    assert read("bfs_scatter_share", ctx) == pytest.approx(50.0)
    assert read("bfs_gather_share", ctx) == pytest.approx(25.0)


def test_readers_leave_the_metric_out_where_nothing_is_to_read():
    """A program without the spans (a parent commit), a window without
    operations: None, before any trace is looked for."""
    ctx = by_hand()
    ctx.traversals = None
    for metric in ("levels_per_call", "upload_bytes_per_call",
                   "flag_roundtrip_ms_per_level", "scattered_accesses",
                   "bfs_kernel_ms_per_level", "bfs_roofline"):
        assert read(metric, ctx) is None, metric
    ctx = by_hand()
    ctx.reduced = ctx.reduced._replace(op_seconds={0: {}})
    for metric in ("bfs_kernel_ms_per_level", "bfs_roofline",
                   "bfs_scatter_share", "bfs_gather_share"):
        assert read(metric, ctx) is None, metric
    # spans that carry no ``access`` field, a call whose flag never moved
    ctx = by_hand()
    ctx.traversals = ctx.traversals._replace(access=None, flag_moves=0)
    assert read("scattered_accesses", ctx) is None
    assert read("flag_roundtrip_ms_per_level", ctx) is None


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
    assert listed["bfs_roofline"]["unit"] == "%"
    assert {listed[m]["source"] for m in NEW_METRICS[:4]} == {"device_trace"}
    cell = cells.load_cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == ["call_p50_ms", "setup_s"]
    assert sorted(m["name"] for m in cell.per_layer) == sorted(NEW_METRICS)
    assert cell.cfg["source"] == conf["source"]
    assert cell.cfg["reduced"] == [] and cell.cfg["nodes"] == 1_000_000
    assert cell.params["n"] == 1_000_192 == 256 * 3907
    assert cell.params["loop"] == "traversal"
    assert len(cell.cfg["sources"]) == 3 and cell.cfg["sources"][0] == 0
    assert cell.cfg["source_apart"] not in cell.cfg["sources"]
    assert cell.params["warmup_calls"] >= 2 * len(cell.cfg["sources"])
    for m in NEW_METRICS:
        assert cells.load_reader(m) is not None
    e2e = next(m for m in man["end_to_end"] if m["name"] == "call_p50_ms")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.01
    # the kernels are the source's two, each over the union of parameters
    text = cells.kernel_source(cell.cfg)
    assert text.count("__kernel void") == 2 and "BFS_1" in text
    assert "g_cost[id] = g_cost[tid] + 1;" in text
    assert "g_over[0] = true;" in text


def test_the_accepted_cells_report_what_they_reported():
    """By name, whatever this PR appended behind them."""
    percall = cells.load_cell("mandelbrot_percall_1chip")
    assert [m["name"] for m in percall.end_to_end] == ["call_p50_ms.percall",
                                                       "setup_s"]
    assert "launch_ms_per_call" in [m["name"] for m in percall.per_layer]
    assert not [m for m in percall.per_layer if m["name"] in NEW_METRICS]
    for name in ("mvt_16k_window", "spmv_hpcg256_window", "nbody_8k_window"):
        cell = cells.load_cell(name)
        assert not [m for m in cell.per_layer if m["name"] in NEW_METRICS]
