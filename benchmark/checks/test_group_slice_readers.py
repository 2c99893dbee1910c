"""The two readers of ``reduce_1gib_percall_1chip`` that say whether the walk
of SHOC's ``reduce`` reads one window a work-group (``group_slice_accesses``)
or a row of 128 a work item (``reduce_gathered_accesses``), held to span lines
made by hand, with and without the ``group`` key, and their two entries in the
manifest found BY NAME (``JAX_PLATFORMS=cpu python3 -m pytest
benchmark/checks/test_group_slice_readers.py -q``).  The last test holds the
cell to what ``test_reduction_cell.py``'s manifest check held it to while one
line of that check pinned the per-layer list with ``==`` (PR 46 to PR 49; both
hold it by name now).  Nothing here yields a device number."""

import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]

import cells  # noqa: E402
import host_phases  # noqa: E402

CELL = "reduce_1gib_percall_1chip"
GROUP = ("slice:0;strided:0;uniform:0;gather:0;scatter:1;carried:0;"
         "local:7;group:2")
GATHER = "slice:0;strided:0;uniform:0;gather:2;scatter:1;carried:0;local:7"


def span(kind, start, ms, lane=0, **stats) -> host_phases.HostSpan:
    return host_phases.HostSpan(kind, start, start + 1e-3 * ms, 1,
                                {"lane": lane, **stats})


def lines_by_hand(field):
    launch = "ck/launch"
    return [[span(launch, 9.6, 5, access="gather:9;group:9"),   # before it
             span(launch, 10.5, 5, lane=1, access="gather:7"),  # another lane
             span(launch, 10.6, 5),                             # no field
             span("ck/compile", 10.7, 5, access="gather:8"),    # no launch
             span(launch, 12.1, 5, access=field),
             span(launch, 12.6, 5, access="gather:5;group:5")]]


def by_hand(field) -> SimpleNamespace:
    reader = cells.load_reader("reduce_gathered_accesses")
    return SimpleNamespace(
        access_field=reader.access_field(lines_by_hand(field), 10.0, 14.0, 0))


def read(metric: str, ctx):
    return cells.load_reader(metric).read(ctx)


def test_the_access_field_is_the_lanes_first_launchs_inside_the_window():
    reader = cells.load_reader("reduce_gathered_accesses")
    want = {"slice": 0, "strided": 0, "uniform": 0, "gather": 0, "scatter": 1,
            "carried": 0, "local": 7, "group": 2}
    assert reader.access_field(lines_by_hand(GROUP), 10.0, 14.0, 0) == want
    assert reader.access_field(lines_by_hand(GROUP), 10.0, 14.0, 1) == {
        "gather": 7}
    assert reader.access_field(lines_by_hand(GROUP), 10.0, 14.0, 2) is None
    assert reader.access_field(lines_by_hand(GROUP), 10.0, 12.0, 0) is None


def test_a_line_with_the_group_key():
    ctx = by_hand(GROUP)
    assert read("reduce_gathered_accesses", ctx) == 0.0
    assert read("group_slice_accesses", ctx) == 2.0


def test_a_line_without_the_group_key_is_a_program_that_gathers():
    """The parent's spans: the field is there, the key is not."""
    ctx = by_hand(GATHER)
    assert read("reduce_gathered_accesses", ctx) == 2.0
    assert read("group_slice_accesses", ctx) == 0.0


def test_nothing_to_read_leaves_both_out():
    ctx = SimpleNamespace(access_field=None)  # no span carries the field
    assert read("reduce_gathered_accesses", ctx) is None
    assert read("group_slice_accesses", ctx) is None
    ctx = by_hand("local:7")  # a field that counts no gather
    assert read("reduce_gathered_accesses", ctx) is None
    assert read("group_slice_accesses", ctx) == 0.0


def test_the_two_entries_are_in_the_manifest():
    listed = {m["name"]: m for m in cells.manifest()["per_layer"]}
    for name, better in (("reduce_gathered_accesses", "lower"),
                         ("group_slice_accesses", "higher")):
        assert listed[name] == {
            "name": name, "unit": "count", "better": better,
            "source": "program_span", "layer": "kernel lowering",
            "moves": "call_p50_ms", "workloads": [CELL]}
        assert cells.load_reader(name) is not None
    mine = [m["name"] for m in cells.load_cell(CELL).per_layer]
    assert [m for m in mine if m in ("reduce_gathered_accesses",
                                     "group_slice_accesses")] == [
        "reduce_gathered_accesses", "group_slice_accesses"]  # by name
    assert {"local_row_accesses", "reduce_roofline"} <= set(mine)
    # no other cell reports them
    for w in cells.manifest()["workloads"]:
        if w["name"] != CELL:
            other = [m["name"] for m in cells.load_cell(w["name"]).per_layer]
            assert not {"reduce_gathered_accesses",
                        "group_slice_accesses"} & set(other)


# the cell's per-layer metrics as PR 45 listed them
OLDER_METRICS = [
    "reduce_kernel_ms_per_call", "reduce_roofline",
    "group_barriers_per_launch", "local_row_accesses",
    "device_idle_share.reduce", "window_compiles.reduce",
    "xla_launch_share.reduce", "launch_ms_per_call.reduce",
    "loose_scalars_per_call.reduce", "dispatch_idle_ms_per_call.reduce",
    "unnamed_idle_share.reduce", "readback_ms_per_call.reduce"]


def test_the_cell_is_what_its_pinned_check_held_it_to():
    """``test_reduction_cell.py::test_the_configuration_the_cell_and_its_
    metrics_are_in_the_manifest``, every assertion of it, with the per-layer
    list held by name (a later PR appends behind it)."""
    config = "shoc_reduction"
    man = cells.manifest()
    row = next(w for w in man["workloads"] if w["name"] == CELL)
    assert row == {**row, "config": config, "traffic": CELL, "chips": 1}
    conf = next(c for c in man["configs"] if c["name"] == config)
    assert conf["reduced"] == [] and conf["file"] == (
        f"benchmark/configs/{config}.json")
    assert all(len(s) <= 200 for s in (row["why"], conf["why"],
                                       conf["source"]))
    listed = {m["name"]: m for m in man["per_layer"]}
    assert all(listed[m]["workloads"] == [CELL]
               and listed[m]["moves"] == "call_p50_ms" for m in OLDER_METRICS)
    assert listed["reduce_roofline"]["unit"] == "%"
    assert {listed[m]["source"] for m in OLDER_METRICS[:2]} == {"device_trace"}
    cell = cells.load_cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == ["call_p50_ms", "setup_s"]
    assert set(OLDER_METRICS) <= {m["name"] for m in cell.per_layer}
    assert cell.cfg["source"] == conf["source"]
    assert cell.cfg["reduced"] == [] and cell.cfg["lanes"] == 1
    assert cell.cfg["elements"] == 2**28  # the name's 1 GiB since PR 49
    assert "elements" in cell.cfg["assumed"]
    assert cell.params["n"] == 16384 == cell.cfg["groups"] * cell.cfg[
        "local_range"] and cell.params["loop"] == "reduction"
    assert cell.params["iterations_per_call"] in (1, 4)
    assert cell.params["warmup_calls"] == 8 and cell.params["pins"] == {}
    plan = cell.ref.call_values(cell.cfg, cell.params, (cell.cfg["elements"],))
    assert len(plan["cycle"]) == 4 and plan["apart"] not in plan["cycle"]
    assert all(n % 32768 == 0 for (n,) in plan["cycle"] + [plan["apart"]])
    for m in OLDER_METRICS:
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
