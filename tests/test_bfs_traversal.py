"""Rodinia's breadth-first search through ``compute()`` on the CPU rig: the
host's level loop, scattered stores under a mask, ``char`` tables, a
one-element stop flag (ISSUE 40).  Exact against the configuration's plain
reference (``benchmark/configs/rodinia_bfs_ref.py``); nothing here yields a
device number.

The traversal below is ``benchmark/loops/traversal.py``'s, written out once
more through upstream's public API (flags as properties, ``compute()``,
``no_compute_mode``), so that the program is held to it without the harness.
"""

import importlib.util
import os
from collections import Counter

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from cekirdekler_tpu import ClArray  # noqa: E402
from cekirdekler_tpu.analysis import flag_row  # noqa: E402
from cekirdekler_tpu.arrays.clarray import ComputeValidationError  # noqa: E402
from cekirdekler_tpu.core.cores import PIPELINE_DRIVER, PIPELINE_EVENT  # noqa: E402
from cekirdekler_tpu.core.cruncher import NumberCruncher  # noqa: E402
from cekirdekler_tpu.hardware import platforms  # noqa: E402
from cekirdekler_tpu.kernel.registry import KernelProgram, lowering_meta  # noqa: E402
from cekirdekler_tpu.trace.spans import TRACER  # noqa: E402

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs")
with open(os.path.join(CONFIGS, "rodinia_bfs.cl"), encoding="utf-8") as _f:
    SRC = _f.read()
_spec = importlib.util.spec_from_file_location(
    "rodinia_bfs_ref", os.path.join(CONFIGS, "rodinia_bfs_ref.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

GRAPH = ("starting", "no_of_edges", "edges")
STATE = ("mask", "updating", "visited", "cost")
NAMES = GRAPH + STATE + ("over",)
KERNELS = "BFS_1 BFS_2"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Compiles:
    """XLA backend compiles as jax reports them (benchmark/run.py's)."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        self.n += event == COMPILE_EVENT


COMPILES = Compiles()


@pytest.fixture(scope="module")
def devs():
    return platforms().cpus()


def padded(nodes: int) -> int:
    """Rodinia rounds the range up to the work-group; a node count that is
    a whole number of groups gets one more, so that the range is never the
    node count here."""
    return (nodes // 256 + 1) * 256


def graph(nodes: int, seed: int = 5, graph_seed: int = 3) -> dict:
    cfg = {"nodes": nodes, "graph_seed": graph_seed, "seed_relabels": True}
    data, _values = ref.inputs(cfg, {"n": padded(nodes)},
                               np.random.default_rng(seed))
    return data


def bind(data: dict) -> tuple[dict, object]:
    """The eight arrays with the configuration's flags, and their group."""
    arr = {k: ClArray(data[k], name=k) for k in NAMES}
    for k in GRAPH:
        arr[k].read_only = True
    for k in STATE:
        arr[k].write = False
    arr["over"].write_all = True
    first, *rest = (arr[k] for k in NAMES)
    return arr, first.next_param(*rest)


def traverse(cr, arr, group, source: int, n: int, nodes: int,
             cid: int = 41) -> int:
    """One call of the loop ``traversal``; returns the levels it ran."""
    for k in ("mask", "updating", "visited"):
        arr[k].host()[:] = 0
    arr["cost"].host()[:] = -1
    arr["mask"][source] = arr["visited"][source] = 1
    arr["cost"][source] = 0
    for k in STATE:
        arr[k].read, arr[k].write = True, False
    arr["over"].read = arr["over"].write = True
    levels = 0
    while True:
        arr["over"][0] = 0
        group.compute(cr, cid, KERNELS, n, 256, values=(nodes,))
        levels += 1
        if levels == 1:
            for k in GRAPH + STATE:
                arr[k].read = False
        if not arr["over"][0]:
            break
    arr["over"].read = arr["over"].write = False
    arr["cost"].write = True
    cr.no_compute_mode = True
    try:
        group.compute(cr, cid, KERNELS, n, 256, values=(nodes,))
    finally:
        cr.no_compute_mode = False
    return levels


# -- Rodinia's small shapes, several sources, exact -------------------------

@pytest.mark.parametrize("nodes,sources", [(4096, (0, 1, 7, 4095)),
                                           (65536, (0, 9))])
def test_traversals_are_exact_against_the_plain_reference(devs, nodes,
                                                          sources):
    data = graph(nodes)
    n = padded(nodes)
    assert n != nodes and data["mask"].size == n
    cr = NumberCruncher(devs.subset(1), SRC)
    try:
        arr, group = bind(data)
        for base in sources:
            source = int(data["relabel"][base])
            levels = traverse(cr, arr, group, source, n, nodes)
            want, want_levels = ref.bfs(
                data["starting"], data["no_of_edges"], data["edges"], source)
            assert levels == want_levels
            np.testing.assert_array_equal(arr["cost"].host(), want)
            assert (arr["cost"].host()[nodes:] == -1).all()
        assert cr.number_of_errors_happened == 0
    finally:
        cr.dispose()


def test_many_lanes_of_one_pass_store_to_one_element(devs):
    """A fan: node 0 -> 1..k -> hub k + 1 -> leaf k + 2.  At the second
    level all k lanes of the frontier store the SAME cost and flag to the
    hub in the same pass; the duplicates must leave exactly that value."""
    k = 700
    nodes, n = k + 3, 1024
    hub, leaf = k + 1, k + 2
    mids = np.arange(1, k + 1, dtype=np.int32)
    src = np.concatenate([np.zeros(k, np.int32), mids, mids,
                          np.full(k, hub, np.int32), [hub], [leaf]])
    dst = np.concatenate([mids, np.zeros(k, np.int32),
                          np.full(k, hub, np.int32), mids, [leaf], [hub]])
    starting, counts, edges = ref.csr(src.astype(np.int32),
                                      dst.astype(np.int32), nodes, n)
    data = {"starting": starting, "no_of_edges": counts, "edges": edges,
            "mask": np.zeros(n, np.int8), "updating": np.zeros(n, np.int8),
            "visited": np.zeros(n, np.int8),
            "cost": np.full(n, -1, np.int32), "over": np.zeros(1, np.int8)}
    cr = NumberCruncher(devs.subset(1), SRC)
    try:
        arr, group = bind(data)
        levels = traverse(cr, arr, group, 0, n, nodes)
        want, want_levels = ref.bfs(starting, counts, edges, 0)
        assert (levels, want_levels) == (4, 4)
        np.testing.assert_array_equal(arr["cost"].host(), want)
        assert arr["cost"][hub] == 2 and arr["cost"][leaf] == 3
        np.testing.assert_array_equal(ref.bfs_queue(starting, counts, edges, 0),
                                      want)
    finally:
        cr.dispose()


# -- how the two kernels were lowered ---------------------------------------

def test_the_access_field_counts_the_scatters_and_the_row_loop_is_a_run():
    prog = KernelProgram(SRC)
    n = 4096
    data = graph(4000)
    arrays = tuple(jax.numpy.asarray(data[k]) for k in NAMES)
    infos = []
    for name in ("BFS_1", "BFS_2"):
        fn, info = prog.launcher(name, n, 256, n, platform="cpu")
        fn(0, arrays, (4000,))
        infos.append(info)
    one, two = infos
    # cost[id] and updating[id]: an int and a char
    assert one.access["scatter"] == 2 and one.scattered == (4, 1)
    assert one.loops_masked == 1 and one.loops_counted == 0
    # the adjacency list is read as a run: the overlapping row view of edges
    assert [(s.param, s.kind) for s in one.views] == [(2, "runs")]
    # over[0] = true is ONE element, the same from every lane: no scatter
    assert two.access["scatter"] == 0 and two.access["uniform"] == 1
    assert two.scattered == () and two.stored_params == [
        "g_graph_mask", "g_updating_graph_mask", "g_graph_visited", "g_over"]
    meta = lowering_meta(infos)
    assert "scatter:2" in meta["access"]
    assert meta["scatter"] == "stores:2;width:4+1"
    assert "scatter" not in lowering_meta([two])
    # a launch of 4096 lanes is no wider than a chunk: nothing to compact
    assert one.compact == two.compact == () and "compact" not in meta


def test_the_compact_field_names_the_loop_and_leaves_the_access_field():
    """ISSUE 41: a launch wider than a chunk builds ``BFS_1``'s adjacency
    loop compactable.  The ``access`` field keeps counting the kernel's
    SITES by the kinds the dense path gives them (the same two scatters,
    the same two gathers); what a chunk of compacted lanes turns into
    gathers of its own, the reads at ``tid`` (``g_no_of_edges[tid]``,
    ``g_starting[tid]``, ``g_cost[tid]``), is the ``compact`` field's."""
    from cekirdekler_tpu.kernel import codegen

    width = codegen._COMPACT_WIDTH
    n = 4 * width
    nodes = n - 192
    data = graph(nodes)
    assert data["mask"].size == n
    arrays = tuple(jax.numpy.asarray(data[k]) for k in NAMES)
    prog = KernelProgram(SRC)
    small, big = {}, {}
    for name in ("BFS_1", "BFS_2"):
        for infos, chunk in ((small, width), (big, n)):
            fn, infos[name] = prog.launcher(name, chunk, 256, n, platform="cpu")
            fn(0, arrays, (nodes,))
    assert big["BFS_1"].compact == (1, width, 3, 0, 1)
    assert small["BFS_1"].compact == big["BFS_2"].compact == ()
    assert big["BFS_1"].access == small["BFS_1"].access
    assert big["BFS_1"].scattered == (4, 1)
    # a span over the ladder's rungs of both kernels
    meta = lowering_meta(list(small.values()) + list(big.values()))
    assert meta["compact"] == f"loops:1;width:{width};gathered:3;scattered:0;ordered:1"
    assert "," not in meta["compact"]
    for kind in ("scatter:2", "gather:2", "uniform:1"):
        assert kind in meta["access"].split(";")
    assert "compact" not in lowering_meta([big["BFS_2"], small["BFS_1"]])


@pytest.mark.parametrize("dtype", [np.int8, np.uint8])
def test_a_byte_table_is_gathered_through_the_rows_of_its_words(dtype):
    """The chip's lowering of ``visited[id]`` (``platform="tpu"``: the row
    of the element's 32-bit word fetched, the byte shifted out) against the
    plain element gather, on a table whose length is no whole number of
    words, with indices beyond both ends (loads clamp)."""
    src = """
    __kernel void pick(__global char* t, __global int* idx, __global char* out,
                       __global int* wide) {
        int i = get_global_id(0);
        out[i] = t[idx[i]];
        wide[i] = t[idx[i]];
    }
    """
    n, m = 1024, 1003
    rng = np.random.default_rng(11)
    table = rng.integers(0, 256, m).astype(np.uint8).view(dtype)
    idx = rng.integers(-40, m + 40, n).astype(np.int32)
    idx[:4] = [0, m - 1, -1, m]
    arrays = tuple(jax.numpy.asarray(a) for a in (
        table, idx, np.zeros(n, dtype), np.zeros(n, np.int32)))
    prog = KernelProgram(src)
    got = {}
    for platform in ("cpu", "tpu"):
        fn, info = prog.launcher("pick", n, 256, n, platform=platform)
        out = fn(0, arrays, ())
        assert info.lowering == "xla" and info.access["gather"] == 2
        got[platform] = [np.asarray(o) for o in out[2:]]
    want = table[np.clip(idx, 0, m - 1)]
    for platform in got:
        np.testing.assert_array_equal(got[platform][0], want)
        # a char is signed where the table is: the wide store keeps it
        np.testing.assert_array_equal(
            got[platform][1], want.view(np.int8).astype(np.int32))


def test_the_chips_lowering_of_both_kernels_equals_the_hosts():
    """One level of a traversal in the middle of its course, through the
    launchers a TPU lane builds (row gathers, byte tables through their
    words) and through the host's: the same five arrays to the last bit."""
    nodes, n = 4000, 4096
    data = graph(nodes)
    want, _ = ref.bfs(data["starting"], data["no_of_edges"], data["edges"], 0)
    level = 3
    state = {"mask": (want == level), "updating": np.zeros(n, bool),
             "visited": (want >= 0) & (want <= level),
             "cost": np.where((want >= 0) & (want <= level), want, -1)}
    host = dict(data, **{k: v.astype(data[k].dtype)
                         for k, v in state.items()})
    prog = KernelProgram(SRC)
    outs = {}
    for platform in ("cpu", "tpu"):
        arrays = tuple(jax.numpy.asarray(host[k]) for k in NAMES)
        for name in ("BFS_1", "BFS_2"):
            fn, info = prog.launcher(name, n, 256, n, platform=platform)
            arrays = tuple(fn(0, arrays, (nodes,)))
        outs[platform] = [np.asarray(a) for a in arrays]
    for a, b in zip(outs["cpu"], outs["tpu"]):
        np.testing.assert_array_equal(a, b)
    cost = outs["tpu"][NAMES.index("cost")]
    np.testing.assert_array_equal(
        cost, np.where((want >= 0) & (want <= level + 1), want, -1))
    assert outs["tpu"][NAMES.index("over")].tolist() == [1]


# -- compiled for the chip, without the chip ---------------------------------

@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) TPU v5e device; the TPU's compiler is
    installed beside the CPU rig."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no compiler here: nothing to say
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_both_kernels_compile_for_the_chip_at_the_cells_size(one_chip):
    """The launchers a TPU lane builds, lowered for a described v5e at the
    cell's own array sizes (one small rung of its ladder): the chip's
    compiler takes them, and the byte table's word view is packed the way
    that compiles in a second (``_words_of``: as ``[n / 4, 4]`` columns it
    took 17-35 s a launcher, seven launchers a kernel: PERF.md, PR 40)."""
    import re

    jnp = jax.numpy
    n, entries, chunk = 1_000_192, 6_000_300, 16384

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    arrays = tuple(shaped((size,), dtype) for size, dtype in (
        (n, jnp.int32), (n, jnp.int32), (entries, jnp.int32), (n, jnp.int8),
        (n, jnp.int8), (n, jnp.int8), (n, jnp.int32), (1, jnp.int8)))
    values = (shaped((), jnp.int32),)
    prog = KernelProgram(SRC)
    for name in ("BFS_1", "BFS_2"):
        fn, info = prog.launcher(name, chunk, 256, n, platform="tpu")
        # as the cell's launches run: ``edges``' run view handed over,
        # ``visited``'s words made in the launch (BFS_2 stores to it)
        views = {s: shaped(v.shape, v.dtype) for s, v in (
            (s, jax.eval_shape(s.build, arrays[s.param]))
            for s in fn.wants(arrays, values, None) if s.param == 2)}
        lowered = fn.trace(shaped((), jnp.int32), arrays, values, None,
                           views).lower()
        text = lowered.as_text()
        if name == "BFS_1":
            assert re.search(r"tensor<\d+x4x128xui32>", text), "no word view"
            assert not re.search(r"tensor<\d+x4xui(8|32)>", text)
        compiled = lowered.compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
        assert info.lowering == "xla" and info.veto
        # the rung is wider than a chunk: BFS_1's loop compiled both ways
        assert bool(info.compact) == (name == "BFS_1")


def test_the_common_passes_of_reduce_compile_to_a_pass_with_no_mask(one_chip):
    """SHOC's ``reduce`` as its cell launches it (64 groups of 256 over 2^28
    floats), compiled for a described v5e (here, beside the file's other such
    compile, so that one worker loads the TPU's compiler): the ``while`` of
    the passes every lane makes (ISSUE 51) holds the two group slices, at most
    two fusions, and nothing of a mask: no ``pred[16384]``, no ``reduce``, no
    ``conditional``, in its body or its condition.  No time is read here."""
    import os
    import re

    jnp = jax.numpy
    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
            __file__))), "benchmark", "configs", "shoc_reduction.cl")) as f:
        prog = KernelProgram(f.read())

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, info = prog.launcher("reduce", 16384, 256, 16384, platform="tpu")
    values = (np.uint32(1 << 28),)
    text = fn.trace(shaped((), jnp.int32), (shaped((1 << 28,), jnp.float32),
                                             shaped((64,), jnp.float32)),
                    values, fn.keys_of(values)).lower().compile().as_text()
    assert info.loops_peeled == 1
    comps = {m.group(1): m.group(2) for m in re.finditer(
        r"\n%?([\w.\-]+) \([^\n]*\) -> [^\n]*\{\n(.*?)\n\}", text, re.S)}
    walks = [(comps[c], comps[b]) for c, b in re.findall(
        r"condition=%?([\w.\-]+), body=%?([\w.\-]+)", text)
        if "f32[64,2,128]" in comps[b]]
    # (the loop that checks its reads pass by pass holds them in the branches
    # of its ``conditional``s: computations of their own)
    assert len(walks) >= 2
    # one of them is the unmasked one; the others carry the mask as they did
    bare = [w for w in walks if "pred[16384]" not in w[0] + w[1]]
    assert len(bare) == 1
    cond, body = bare[0]
    assert len(re.findall(r" fusion\(", body)) <= 2
    for part in (cond, body):
        assert not re.search(r" (reduce|conditional|while)\(", part)


UNIFORM = """
__kernel void raise(__global int* a, __global char* flag, __global int* at,
                    int lim, int where) {
    int i = get_global_id(0);
    if (a[i] > lim) { flag[where] = true; at[0] = lim + 1; }
}
"""


@pytest.mark.parametrize("lim,where,flag,at", [
    (5, 0, [1, 0, 0], 6),      # some lanes active: one element written
    (2000, 0, [0, 0, 0], -3),  # no lane active: nothing stored
    (5, 2, [0, 0, 1], 6),      # a runtime index, the same in every lane
    (5, 3, [0, 0, 0], 6),      # beyond the flag's length: dropped
    (5, -1, [0, 0, 0], 6),     # before its start: dropped
])
def test_a_uniform_store_writes_one_element_if_any_lane_is_active(
        devs, lim, where, flag, at):
    cr = NumberCruncher(devs.subset(1), UNIFORM)
    try:
        a = ClArray(np.arange(1024, dtype=np.int32), name="a", read_only=True)
        f = ClArray(np.zeros(3, np.int8), name="flag", write_all=True)
        t = ClArray(np.full(1, -3, np.int32), name="at", write_all=True)
        a.next_param(f, t).compute(cr, 43, "raise", 1024, 256,
                                   values=(lim, where))
        assert f.host().tolist() == flag and int(t[0]) == at
        info = cr.cores.program.launcher("raise", 1024, 256, 1024,
                                         platform="cpu")[1]
        assert info.access["scatter"] == 0 and info.access["uniform"] == 2
    finally:
        cr.dispose()


# -- the same compute id, flags that change between calls -------------------

def test_a_flag_flip_compiles_nothing_and_uploads_nothing_resident(devs):
    nodes = 4096
    data, n = graph(nodes), padded(nodes)
    cr = NumberCruncher(devs.subset(1), SRC)
    try:
        arr, group = bind(data)
        source = int(data["relabel"][0])
        traverse(cr, arr, group, source, n, nodes)  # compiles, uploads all
        w = cr.cores.workers[0]
        views = cr.cores.program.kept_views
        c0, up0, whole0 = COMPILES.n, w._m_upload_bytes.value, (
            w._m_whole_up.value, w._m_whole_down.value)
        TRACER.enable(clear=True)
        try:
            levels = traverse(cr, arr, group, int(data["relabel"][1]), n,
                              nodes)
        finally:
            TRACER.disable()
        spans = TRACER.snapshot()
        assert COMPILES.n == c0
        # the four state arrays at the start and one byte a level: the
        # graph (read = false since the first traversal) never crosses
        state = 3 * n + 4 * n
        assert w._m_upload_bytes.value - up0 == state + levels
        assert w._m_whole_up.value - whole0[0] == state + levels
        assert w._m_whole_down.value - whole0[1] == levels + 4 * n
        ups = [s.tag for s in spans if s.kind == "upload"]
        assert sorted(set(ups)) == sorted(STATE + ("over",))
        assert ups.count("over") == levels and not set(ups) & set(GRAPH)
        downs = [s.tag for s in spans if s.kind == "download"]
        assert downs.count("over") == levels and downs.count("cost") == 1
        assert len([s for s in spans if s.kind == "launch"]) == levels
        # the run view of edges was built once, when edges went up
        assert cr.cores.program.kept_views is views
        want, _ = ref.bfs(data["starting"], data["no_of_edges"],
                          data["edges"], int(data["relabel"][1]))
        np.testing.assert_array_equal(arr["cost"].host(), want)
    finally:
        cr.dispose()


# -- the short array -------------------------------------------------------

INC = """
__kernel void inc(__global int* a, __global char* flag) {
    int i = get_global_id(0);
    a[i] = a[i] + 1;
    if (a[i] > 1000) flag[0] = true;
}
"""


@pytest.mark.parametrize("lanes", [1, 2])
@pytest.mark.parametrize("enqueue", [False, True])
def test_a_one_element_write_all_array_is_admitted(devs, lanes, enqueue):
    cr = NumberCruncher(devs.subset(lanes), INC)
    try:
        a = ClArray(np.arange(1024, dtype=np.int32), name="a",
                    partial_read=True)
        flag = ClArray(np.zeros(1, np.int8), name="flag", write_all=True)
        cr.enqueue_mode = enqueue
        for _ in range(3):
            a.next_param(flag).compute(cr, 44, "inc", 1024, 64)
        cr.enqueue_mode = False
        np.testing.assert_array_equal(a.host(), np.arange(1024) + 3)
        # (on two lanes the flag's owner is the lane of the upper half)
        assert flag.host().tolist() == [1]
    finally:
        cr.dispose()


@pytest.mark.parametrize("flags", [
    {}, {"write": False}, {"write_all": True, "partial_read": True},
    {"read_only": True}])
def test_a_short_array_without_a_whole_write_all_is_still_refused(devs,
                                                                  flags):
    cr = NumberCruncher(devs.subset(1), INC)
    try:
        a = ClArray(np.arange(1024, dtype=np.int32), name="a")
        flag = ClArray(np.zeros(1, np.int8), name="flag", **flags)
        with pytest.raises(ComputeValidationError, match="flag"):
            a.next_param(flag).compute(cr, 45, "inc", 1024, 64)
    finally:
        cr.dispose()


# -- the verdict, by lane count ----------------------------------------------

def rows_of(state_write: bool) -> tuple:
    flags = [ClArray(np.zeros(4, np.int32), read_only=True).flags] * 3
    flags += [ClArray(np.zeros(4, np.int32), write=state_write).flags] * 4
    flags += [ClArray(np.zeros(1, np.int8), write_all=True).flags]
    return tuple(flag_row(f) for f in flags)


@pytest.mark.parametrize("lanes,kinds", [
    (1, set()),
    (2, {"scatter-write", "off-partition-write"}),
    (None, {"scatter-write", "off-partition-write"}),
])
def test_scatter_write_is_an_error_on_more_than_one_lane(lanes, kinds):
    prog = KernelProgram(SRC)
    v = prog.verify(("BFS_1", "BFS_2"), rows_of(True), lanes=lanes)
    assert {f.kind for f in v.errors} == kinds
    if kinds:
        scattered = {f.param for f in v.errors if f.kind == "scatter-write"}
        assert scattered == {"g_cost", "g_updating_graph_mask"}
        assert {f.param for f in v.errors
                if f.kind == "off-partition-write"} == {"g_over"}
    # an array that is not written back has nothing to lose at a readback
    quiet = prog.verify(("BFS_1", "BFS_2"), rows_of(False), lanes=lanes)
    assert "scatter-write" not in {f.kind for f in quiet.errors}


def test_two_lanes_record_the_finding_and_one_lane_does_not(devs):
    from cekirdekler_tpu.obs.flight import FLIGHT

    def findings(lanes):
        data = graph(4096)
        cr = NumberCruncher(devs.subset(lanes), SRC)
        try:
            arr, group = bind(data)
            for k in STATE:
                arr[k].write = True
            FLIGHT.clear()
            group.compute(cr, 46, KERNELS, padded(4096), 256, values=(4096,))
            return [e for e in FLIGHT.snapshot()
                    if e.kind == "kernel-verify"]
        finally:
            cr.dispose()

    assert findings(1) == []
    two = findings(2)
    assert two and "scatter-write" in str(two)


# -- B.5: a scatter into a partial_read array under an engine that cuts ------

ROAM = """
__kernel void roam(__global int* a, __global int* b, __global int* t) {
    int i = get_global_id(0);
    b[i] = b[i] + 1;
    a[t[i]] = i + 101;
}
"""


ENGINES = {
    "streamed": {},
    "driver": dict(pipeline=True, pipeline_blobs=4,
                   pipeline_type=PIPELINE_DRIVER),
    "event": dict(pipeline=True, pipeline_blobs=4,
                  pipeline_type=PIPELINE_EVENT),
}


@pytest.mark.parametrize("engine", list(ENGINES))
def test_a_scattered_store_is_exact_under_an_engine_that_cuts_the_range(
        devs, engine):
    """Part 0's launch stores into the LAST part's elements (``t`` is the
    reversal).  Moved part by part (chunks, blobs), the last part's upload
    would bury those stores and the first part's download would miss the
    last launch's: every engine that cuts the range moves such an array
    whole (``roaming_stores``, asked by ``phase.classify`` alone) while
    ``b``, whose stores stay with their items, still goes part by part."""
    n = 1024
    prog = KernelProgram(ROAM)
    assert prog.roaming_stores(("roam",), (1, 1, 1)) == frozenset({0})
    assert KernelProgram(SRC).roaming_stores(
        ("BFS_1", "BFS_2"), (1,) * 8) == frozenset({4, 6, 7})
    cr = NumberCruncher(devs.subset(1), ROAM)
    try:
        cr.stream_chunks = 4
        a = ClArray(np.full(n, 100, np.int32), name="a", partial_read=True)
        b = ClArray(np.zeros(n, np.int32), name="b", partial_read=True)
        t = ClArray(np.arange(n - 1, -1, -1, dtype=np.int32), name="t",
                    read_only=True)
        TRACER.enable(clear=True)
        try:
            a.next_param(b, t).compute(cr, 47, "roam", n, 64,
                                       **ENGINES[engine])
        finally:
            TRACER.disable()
        if engine == "streamed":
            assert cr.cores.last_stream_chunks == {0: 4}  # it did stream
        moved = Counter(
            s.tag.removeprefix("stage:").partition("@")[0]
            for s in TRACER.snapshot()
            if s.kind in ("upload", "upload-chunk", "download",
                          "download-chunk")
            and not s.tag.startswith("part:"))
        # once up and once back, against four parts each way
        assert moved == {"a": 2, "b": 8, "t": 1}
        want = np.empty(n, np.int32)
        want[n - 1 - np.arange(n)] = np.arange(n) + 101
        np.testing.assert_array_equal(a.host(), want)
        np.testing.assert_array_equal(b.host(), 1)
    finally:
        cr.dispose()
