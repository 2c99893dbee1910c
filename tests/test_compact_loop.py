"""Lane compaction of the vectorized-XLA lowering's masked loop (PR 41).

A masked loop entered under a lane-varying mask, whose passes gather or
scatter, runs over the lanes that ENTER it: their numbers are put in order
once and the loop walks them in chunks of ``codegen._COMPACT_WIDTH``
(``_exec_compacted``); with most lanes entering it runs over all of them as
it always did, and the count decides at run time.  Compaction is another
route to the SAME arrays: every case here is held bit for bit to the build
with the mechanism switched off (a chunk wider than any launch), on the CPU
rig; nothing here yields a device number.

The ORDER of the entering lanes (ISSUE 53): where the loop's syntax gives the
passes each lane is going to make (``codegen._common_walks``), the lanes go
to their chunks by that count (``_chunk_lanes(entered, width, trips)``), from
long to short.  ``order`` = ``keyed`` is the build as it stands, ``rising``
the same build with the key switched off (the rising lane order of PR 41):
both are held to the dense loop, and the property kernels to the scalar
oracle.
"""

import importlib.util
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from cekirdekler_tpu.kernel import codegen, lang  # noqa: E402
from cekirdekler_tpu.kernel.registry import KernelProgram, lowering_meta  # noqa: E402
from tests.kernel_oracle import Oracle  # noqa: E402

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs")


def source(name: str) -> str:
    with open(os.path.join(CONFIGS, name), encoding="utf-8") as f:
        return f.read()


_spec = importlib.util.spec_from_file_location(
    "rodinia_bfs_ref", os.path.join(CONFIGS, "rodinia_bfs_ref.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

OFF = 1 << 30  # a chunk no launch is wider than: the mechanism switched off
LOCAL = 64


@pytest.fixture
def compaction(monkeypatch):
    """``set(width, dense_share)``: the module's two constants for a build."""
    def set_to(width: int, share: float = 0.5):
        monkeypatch.setattr(codegen, "_COMPACT_WIDTH", width)
        monkeypatch.setattr(codegen, "_COMPACT_DENSE_SHARE", share)
    return set_to


ORDERS = ("keyed", "rising")


def set_order(monkeypatch, order: str) -> None:
    """``rising`` hands the list's builder, as it stands now, no key (the
    loops are still counted as ordered: the analysis is the build's)."""
    assert order in ORDERS
    if order == "rising":
        builder = codegen._chunk_lanes
        monkeypatch.setattr(codegen, "_chunk_lanes",
                            lambda entered, width, trips=None: builder(entered, width))


def held_to_the_oracle(src: str, arrays, got, n: int) -> None:
    """``got``, the arrays a launch of ``src``'s kernel over ``[0, n)`` left,
    against the scalar oracle's."""
    kdef = lang.parse_kernels(src)[0]
    host = {p.name: a.copy() for p, a in zip(kdef.params, arrays)}
    Oracle(kdef, local_size=LOCAL).run(host, {}, n)
    for p, a in zip(kdef.params, got):
        np.testing.assert_array_equal(a, host[p.name], err_msg=p.name)


def launch(src: str, names, arrays, n: int, values=(), platform="cpu"):
    """One launch of each kernel over ``[0, n)``: the arrays it leaves and
    the builds' infos."""
    prog = KernelProgram(src)
    arrays = tuple(jax.numpy.asarray(a) for a in arrays)
    infos = []
    for name in names:
        fn, info = prog.launcher(name, n, LOCAL, n, platform=platform)
        arrays = tuple(fn(0, arrays, values))
        infos.append(info)
    return [np.asarray(a) for a in arrays], infos


def both(compaction, src, names, arrays, n, width, values=(), share=1.0,
         platform="cpu"):
    """The arrays of the dense build, after holding the compacting build's
    to them bit for bit; the compacting build's infos."""
    compaction(OFF)
    dense, off_infos = launch(src, names, arrays, n, values, platform)
    assert all(i.compact == () for i in off_infos)
    compaction(width, share)
    got, infos = launch(src, names, arrays, n, values, platform)
    for at, (a, b) in enumerate(zip(dense, got)):
        np.testing.assert_array_equal(b, a, err_msg=f"array {at}")
    # the access field counts the kernel's SITES by the kinds the dense
    # path gives them, compacting or not
    assert [i.access for i in infos] == [i.access for i in off_infos]
    assert [i.scattered for i in infos] == [i.scattered for i in off_infos]
    return dense, infos


# -- Rodinia's two kernels at frontiers of every size -------------------------

BFS_NAMES = ("starting", "no_of_edges", "edges", "mask", "updating",
             "visited", "cost", "over")
NODES, RANGE, WIDTH = 8000, 8192, 1024


@pytest.fixture(scope="module")
def graph():
    cfg = {"nodes": NODES, "graph_seed": 3, "seed_relabels": True}
    data, _values = ref.inputs(cfg, {"n": RANGE}, np.random.default_rng(5))
    return data


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("share,lanes", [
    (0.0, 0), ("one lane", 1), (0.001, 8), (0.1, 819), (0.49, 4014),
    (1.0, NODES)])
@pytest.mark.parametrize("dense_share", [0.5, 1.0])
def test_a_bfs_level_is_the_same_to_the_last_bit(compaction, monkeypatch, graph,
                                                 share, lanes, dense_share,
                                                 platform, order):
    """One level (``BFS_1`` then ``BFS_2``) from a frontier of ``lanes``
    seeded nodes of one launch: the five state arrays of the compacting
    build against the dense one's, with the count choosing the path
    (``dense_share`` 0.5: a frontier of 49 % runs compacted, one of 100 %
    over all lanes) and with every frontier compacted (1.0).  Chunks of 1024:
    the frontiers fill none, part of one, four with the last part full, and
    (8000 nodes, every frontier compacted) eight."""
    set_order(monkeypatch, order)
    rng = np.random.default_rng(17)
    frontier = np.zeros(RANGE, bool)
    frontier[rng.choice(NODES, lanes, replace=False)] = True
    visited = frontier | (rng.random(RANGE) < 0.3)
    state = dict(graph, mask=frontier.astype(np.int8),
                 updating=np.zeros(RANGE, np.int8),
                 visited=visited.astype(np.int8),
                 cost=np.where(visited, 3, -1).astype(np.int32))
    arrays = [state[k] for k in BFS_NAMES]
    dense, infos = both(compaction, source("rodinia_bfs.cl"),
                        ("BFS_1", "BFS_2"), arrays, RANGE, WIDTH, (NODES,),
                        dense_share, platform)
    one, two = infos
    assert one.compact == (1, WIDTH, 3, 0, 1) and two.compact == ()
    found = dense[BFS_NAMES.index("mask")].astype(bool)
    assert found.any() == (0 < lanes < NODES)  # all visited: none to find
    assert not (found & visited).any()


# -- the properties, a kernel each ----------------------------------------------

HEAD = """
__kernel void k(__global int* lo, __global int* cnt, __global int* col,
                __global int* tab, __global int* on, __global int* out,
                __global int* aux) {
    int i = get_global_id(0);
"""
KERNELS = {
    # a carried local (two: the sum and the loop's variable) read after
    "local read after the loop": HEAD + """
    if (on[i]) {
        int s = 0;
        int j;
        for (j = lo[i]; j < lo[i] + cnt[i]; j++) { s += tab[col[j]]; }
        out[i] = s + j;
    }
}""",
    # the loop touches out[] at the lane's own element only: it rides the
    # loop as a local, picked out and put back a chunk
    "own-element store that rides the loop": HEAD + """
    if (on[i]) {
        for (int j = lo[i]; j < lo[i] + cnt[i]; j++) { out[i] += tab[col[j]]; }
    }
}""",
    # out[] is stored at the lane's own element and read elsewhere: a
    # scatter of distinct indices in a chunk, a slice on the dense path
    "own-element store beside a read elsewhere": HEAD + """
    if (on[i]) {
        for (int j = lo[i]; j < lo[i] + cnt[i]; j++) {
            out[i] = tab[col[j]] + aux[i];
        }
        aux[i] = out[i] + 1;
    }
}""",
    "break and continue": HEAD + """
    if (on[i]) {
        int s = 0;
        for (int j = lo[i]; j < lo[i] + cnt[i]; j++) {
            int v = tab[col[j]];
            if (v > 90) { break; }
            if (v < 10) { continue; }
            s += v;
        }
        out[i] = s;
    }
}""",
    # lanes that returned before the loop do not enter it
    "return before the loop": HEAD + """
    if (!on[i]) { return; }
    int s = 0;
    for (int j = lo[i]; j < lo[i] + cnt[i]; j++) { s += tab[col[j]]; }
    out[i] = s;
}""",
    "a counted loop inside": HEAD + """
    if (on[i]) {
        int s = 0;
        for (int j = lo[i]; j < lo[i] + cnt[i]; j++) {
            for (int t = 0; t < 3; t++) { s += tab[col[j]] * aux[t]; }
        }
        out[i] = s;
    }
}""",
    "a while loop and a scattered store": HEAD + """
    if (on[i]) {
        int j = lo[i];
        while (j < lo[i] + cnt[i]) { aux[col[j]] = 7; j++; }
        out[i] = j;
    }
}""",
    "a private array": HEAD + """
    if (on[i]) {
        int h[4];
        for (int j = lo[i]; j < lo[i] + cnt[i]; j++) {
            int v = tab[col[j]];
            h[v & 3] += v;
        }
        out[i] = h[0] + 2 * h[1] + 3 * h[2] + 5 * h[3];
    }
}""",
}


def csr(n: int, seed: int, share: float):
    """Lists of 0 to 6 entries a lane into a table of ``n``; ``share`` of the
    lanes switched on."""
    rng = np.random.default_rng(seed)
    cnt = rng.integers(0, 7, n).astype(np.int32)
    lo = (np.cumsum(cnt) - cnt).astype(np.int32)
    col = rng.integers(0, n, int(cnt.sum()) + 8).astype(np.int32)
    tab = rng.integers(0, 100, n).astype(np.int32)
    on = (rng.random(n) < share).astype(np.int32)
    out = rng.integers(0, 5, n).astype(np.int32)
    aux = rng.integers(1, 4, n).astype(np.int32)
    return [lo, cnt, col, tab, on, out, aux]


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("what", sorted(KERNELS))
# the last chunk shorter than W (700 lanes of 2048 on: chunks of 512); a
# range that is no multiple of W (1280 = 2.5 W) with a last chunk of its own
@pytest.mark.parametrize("n,width,share", [(2048, 512, 0.34), (1280, 512, 0.6)])
def test_a_compacted_loop_leaves_what_the_dense_one_leaves(
        compaction, monkeypatch, what, n, width, share, order):
    arrays = csr(n, 23, share)
    on = arrays[4].astype(bool)
    assert on.sum() % width and n > width
    set_order(monkeypatch, order)
    dense, infos = both(compaction, KERNELS[what], ("k",), arrays, n, width)
    (info,) = infos
    assert info.compact == (1, width) + info.compact[2:4] + (1,)
    assert lowering_meta(infos)["compact"].endswith(";ordered:1")
    held_to_the_oracle(KERNELS[what], arrays, dense, n)
    # the lanes switched off keep what they had
    np.testing.assert_array_equal(dense[5][~on], arrays[5][~on])
    assert (dense[5][on] != arrays[5][on]).any()


def test_a_return_inside_the_loop_is_refused_as_on_the_dense_path(compaction):
    src = HEAD + """
    if (on[i]) {
        for (int j = lo[i]; j < lo[i] + cnt[i]; j++) {
            if (tab[col[j]] > 90) { return; }
            out[i] += 1;
        }
    }
}"""
    from cekirdekler_tpu.errors import KernelLanguageError

    for width in (OFF, 512):
        compaction(width, 1.0)
        with pytest.raises(KernelLanguageError, match="'return' inside a loop"):
            launch(src, ("k",), csr(2048, 3, 0.3), 2048)


def test_the_count_of_entering_lanes_chooses_the_path(compaction):
    """The same build, three launches: no lane on, a few, nearly all.  The
    compacting build's HLO holds both loops; which ran is not observable
    from the arrays (that is the point), so the three are held to the dense
    build's."""
    for share in (0.0, 0.05, 0.97):
        arrays = csr(2048, 29, share)
        both(compaction, KERNELS["local read after the loop"], ("k",),
             arrays, 2048, 512, share=0.5)


# -- the gate: loops that cannot gain are built as they always were ------------

ARITHMETIC = """
__kernel void k(__global float* a, __global int* on, __global float* out) {
    int i = get_global_id(0);
    if (on[i]) {
        float z = a[i];
        int it = 0;
        while (z * z < 4.0f && it < 50) { z = z * z + a[i]; it++; out[i] = z; }
    }
}"""


def _gate_cases():
    rng = np.random.default_rng(31)
    n = 4096
    cnt = rng.integers(0, 7, n)
    rowptr = np.concatenate([[0], np.cumsum(cnt)]).astype(np.int32)
    spmv = [rowptr, rng.integers(0, n, rowptr[-1]).astype(np.int32),
            rng.random(rowptr[-1]).astype(np.float32),
            rng.random(n).astype(np.float32), np.zeros(n, np.float32)]
    return {
        # no entry mask: every lane enters
        "spmv": (source("hpcg_spmv.cl"), "spmv", spmv, (2.0,),
                 "counted:0;masked:1"),
        # no buffer access in the loop
        "mandelbrot": (source("mandelbrot_frame.cl"), "mandelbrot",
                       [np.zeros(n, np.float32)],
                       (-2.0, -1.25, 0.04, 0.04, 64, 40), "counted:0;masked:1"),
        # guarded, arithmetic and own-element accesses only
        "arithmetic": (ARITHMETIC, "k",
                       [rng.random(n).astype(np.float32) - 0.5,
                        (rng.random(n) < 0.2).astype(np.int32),
                        np.zeros(n, np.float32)], (), "counted:0;masked:1"),
    }


@pytest.mark.parametrize("which", ["spmv", "mandelbrot", "arithmetic"])
def test_a_loop_that_cannot_gain_is_built_as_before(compaction, which):
    src, name, arrays, values, loops = _gate_cases()[which]
    n = 4096
    traced = {}
    for width in (OFF, 512):
        compaction(width)
        fn, info = KernelProgram(src).launcher(name, n, LOCAL, n, platform="cpu")
        args = (0, tuple(jax.numpy.asarray(a) for a in arrays), values)
        out = [np.asarray(a) for a in fn(*args)]
        traced[width] = (str(fn.trace(*args).jaxpr), out, dict(info.access))
        meta = lowering_meta([info])
        assert info.compact == () and "compact" not in meta
        assert meta["loops"] == loops
    # today's path to the last operation: the same program, the same fields
    assert traced[OFF][0] == traced[512][0]
    assert traced[OFF][2] == traced[512][2]
    for a, b in zip(traced[OFF][1], traced[512][1]):
        np.testing.assert_array_equal(a, b)


def test_a_launch_of_one_chunk_or_less_is_not_compacted(compaction):
    """A rung no wider than a chunk would be one chunk: built dense."""
    compaction(2048, 1.0)
    _out, infos = launch(KERNELS["break and continue"], ("k",),
                         csr(2048, 5, 0.3), 2048)
    assert infos[0].compact == ()


def test_the_prefix_counts_are_exact():
    rng = np.random.default_rng(41)
    for n, share in ((128, 0.5), (1280, 0.01), (16384 + 128, 0.7),
                     (128 * 129, 1.0)):
        x = (rng.random(n) < share).astype(np.int32)
        got = np.asarray(codegen._prefix_counts(jax.numpy.asarray(x)))
        np.testing.assert_array_equal(got, np.cumsum(x))


# -- the order of the entering lanes (ISSUE 53) ---------------------------------

def classes_of(trips) -> np.ndarray:
    """The class of a count of passes as ``codegen._keyed_ranks`` has it: the
    count itself under ``_SHORT``, beyond it one class a bit length."""
    t = np.minimum(np.asarray(trips, np.uint64), 0xFFFFFFFF)
    bits = np.array([int(x).bit_length() for x in t])
    return np.where(t < codegen._SHORT, t,
                    codegen._SHORT + bits - codegen._SHORT.bit_length())


def the_list(entered, width, trips=None) -> np.ndarray:
    """The whole list ``_chunk_lanes`` builds, chunk after chunk."""
    lanes_of = codegen._chunk_lanes(
        jax.numpy.asarray(entered), width,
        None if trips is None else jax.numpy.asarray(trips))
    chunks = -(-len(entered) // width)
    return np.concatenate([np.asarray(lanes_of(c)) for c in range(chunks)])


ORDER_CASES = {
    # several chunks, the last part full; lists as the BFS cell's
    "lists of 2 to 18": (4096, 512, 0.4, lambda rng, n: rng.integers(2, 19, n)),
    # zero-trip lanes enter and are placed too (behind every other)
    "lists of 0 to 6": (2048, 512, 0.6, lambda rng, n: rng.integers(0, 7, n)),
    # every class above ``_SHORT`` and the largest counts a walker can make
    "geometric classes": (2048, 256, 0.7, lambda rng, n: rng.choice(
        [1, 31, 32, 33, 63, 64, 1000, 1 << 20, (1 << 31) - 1, 1 << 31,
         (1 << 32) - 1], n)),
    # a range that is no multiple of 128 nor of the width
    "a ragged range": (1000, 96, 0.5, lambda rng, n: rng.integers(1, 41, n)),
    # one class: the rising order
    "every lane the same count": (2048, 512, 0.5,
                                  lambda rng, n: np.full(n, 5)),
}


@pytest.mark.parametrize("case", sorted(ORDER_CASES))
def test_the_keyed_order_holds_every_entering_lane_once_by_class(case):
    n, width, share, draw = ORDER_CASES[case]
    rng = np.random.default_rng(53)
    entered = rng.random(n) < share
    trips = np.asarray(draw(rng, n)).astype(np.uint32)
    k = int(entered.sum())
    assert k > width
    got = the_list(entered, width, trips)
    # every entering lane exactly once, none that did not enter; beyond the
    # last of them, lanes of the launch (masked off, but picked out)
    np.testing.assert_array_equal(np.sort(got[:k]), np.flatnonzero(entered))
    assert len(got) % width == 0 and (got >= 0).all() and (got < n).all()
    # classes from long to short; inside a class the lanes rise
    cls = classes_of(trips)[got[:k]]
    assert (np.diff(cls) <= 0).all()
    same = np.diff(cls) == 0
    assert (np.diff(got[:k])[same] > 0).all()
    if case == "every lane the same count":
        np.testing.assert_array_equal(got, the_list(entered, width))


@pytest.mark.parametrize("k", [0, 1, 200, 512])
def test_one_chunk_of_entering_lanes_is_todays_list_to_the_element(k):
    """With no more entering lanes than a chunk holds there is nothing to
    order: the key is not looked at."""
    rng = np.random.default_rng(7)
    entered = np.zeros(4096, bool)
    entered[rng.choice(4096, k, replace=False)] = True
    trips = rng.integers(0, 41, 4096).astype(np.uint32)
    np.testing.assert_array_equal(the_list(entered, 512, trips),
                                  the_list(entered, 512))


def spy_on_the_lists(monkeypatch):
    """``seen``: a ``(trips, entered)`` pair a launch that built a list, and
    ``chunks``: the lanes of every chunk a launch ran, by host callbacks."""
    seen, chunks = [], []
    real = codegen._chunk_lanes

    def spied(entered, width, trips=None):
        if trips is not None:
            jax.debug.callback(
                lambda t, e: seen.append((np.asarray(t), np.asarray(e))),
                trips, entered)
        lanes_of = real(entered, width, trips)

        def of(c):
            lanes = lanes_of(c)
            jax.debug.callback(
                lambda c, l, e: chunks.append(
                    np.asarray(l)[:max(0, int(e.sum()) - int(c) * width)]),
                c, lanes, entered)
            return lanes
        return of

    monkeypatch.setattr(codegen, "_chunk_lanes", spied)
    return seen, chunks


def walk(cond: str, step: str, lo="int", extra="") -> str:
    """A kernel whose lanes switched on walk ``j`` from ``lo[i]`` while
    ``cond`` holds, stepping by ``step``, and gather a pass."""
    return f"""
__kernel void k(__global {lo}* lo, __global {lo}* hi, __global int* col,
                __global int* on, __global int* out) {{
    int i = get_global_id(0);
    if (on[i]) {{
        int s = 0;
        for ({lo} j = lo[i]; {cond}; {step}) {{ s += col[(j & 1023)]; {extra} }}
        out[i] = s;
    }}
}}"""


TOP = (1 << 31) - 1
KEYS = {
    # name: (source, lo, hi, the passes each lane makes)
    "a walker under, at and past its bound": (
        walk("j < hi[i]", "j++"), [0, 5, 9, 3], [4, 5, 2, 9], [4, 0, 0, 6]),
    "the bound reached is one pass more under <=": (
        walk("j <= hi[i]", "j++"), [0, 5, 9, 3], [4, 5, 2, 9], [5, 1, 0, 7]),
    "the comparison written from the other side": (
        walk("hi[i] > j", "j++"), [0, 5, 9, 3], [4, 5, 2, 9], [4, 0, 0, 6]),
    "a step over one": (
        walk("j < hi[i]", "j += 3"), [0, 0, 0, 1], [9, 10, 1, 2], [3, 4, 1, 1]),
    "a step over one under <=": (
        walk("j <= hi[i]", "j += 3"), [0, 0, 0, 2], [9, 8, 0, 1], [4, 3, 1, 0]),
    "a walk made of two moves": (
        walk("j < hi[i]", "j += 3", extra="j--;"), [0, 0, 4, 1], [9, 10, 4, 2],
        [5, 5, 0, 1]),
}


@pytest.mark.parametrize("case", sorted(KEYS))
def test_the_key_is_the_passes_each_lane_is_going_to_make(
        compaction, monkeypatch, case):
    src, lo, hi, passes = KEYS[case]
    n, width = 2048, 512
    seen, _chunks = spy_on_the_lists(monkeypatch)
    compaction(width, 1.0)
    rng = np.random.default_rng(3)
    arrays = [np.resize(np.array(lo, np.int32), n),
              np.resize(np.array(hi, np.int32), n),
              rng.integers(0, 9, n).astype(np.int32), np.ones(n, np.int32),
              np.zeros(n, np.int32)]
    out, (info,) = launch(src, ("k",), arrays, n)
    assert info.compact[4] == 1
    (trips, entered), = seen
    assert entered.all()
    np.testing.assert_array_equal(trips, np.resize(passes, n))
    # the kernel made those passes: the oracle's sum is the build's
    held_to_the_oracle(src, arrays, out, n)


def test_the_key_of_values_near_the_ends_of_int(compaction, monkeypatch):
    """The difference is taken modulo 2^32 and read without a sign: a walk
    from the least ``int`` to the largest is 2^32 - 1 passes, not -1.  (The
    launch is traced to its list and stops there: nobody waits for that
    lane.)"""
    n, width = 2048, 512
    seen, _chunks = spy_on_the_lists(monkeypatch)
    compaction(width, 1.0)
    lo = np.resize(np.array([-TOP - 1, TOP - 2, -TOP - 1, 0], np.int32), n)
    hi = np.resize(np.array([TOP, TOP, -TOP + 1, TOP], np.int32), n)
    want = np.resize(np.array([(1 << 32) - 1, 2, 2, TOP], np.uint32), n)
    real = codegen._exec_masked

    def no_chunk(ctx, *a, **k):  # the chunks' loop would make those passes
        return real(ctx, *a, **k) if ctx.B == n else None

    monkeypatch.setattr(codegen, "_exec_masked", no_chunk)
    on = np.zeros(n, np.int32)
    on[: n // 2] = 1  # (over a share of 1.0 the dense loop would run: none does)
    launch(walk("j < hi[i]", "j++"), ("k",),
           [lo, hi, np.zeros(n, np.int32), on, np.zeros(n, np.int32)], n)
    (trips, entered), = seen
    np.testing.assert_array_equal(trips[entered], want[entered])
    got = the_list(entered, width, trips)
    assert (np.diff(classes_of(trips)[got[:entered.sum()]]) <= 0).all()


UNKEYED = {
    "a bound the body assigns": HEAD + """
    if (on[i]) {
        int e = lo[i] + cnt[i];
        for (int j = lo[i]; j < e; j++) { out[i] += tab[col[j]]; e -= (j & 1); }
    }
}""",
    "a bound read from a buffer the loop stores to": HEAD + """
    if (on[i]) {
        for (int j = lo[i]; j < lo[i] + aux[i]; j++) { aux[col[j]] = 1; out[i] += 1; }
    }
}""",
    "two terms in the condition": HEAD + """
    if (on[i]) {
        int s = 0;
        for (int j = lo[i]; j < lo[i] + cnt[i] && s < 200; j++) { s += tab[col[j]]; }
        out[i] = s;
    }
}""",
    "a walker moved by a run-time step": HEAD + """
    if (on[i]) {
        for (int j = lo[i]; j < lo[i] + cnt[i]; j += aux[i]) { out[i] += tab[col[j]]; }
    }
}""",
    "a walk downward": HEAD + """
    if (on[i]) {
        for (int j = lo[i] + cnt[i]; j > lo[i]; j--) { out[i] += tab[col[j - 1]]; }
    }
}""",
}


@pytest.mark.parametrize("what", sorted(UNKEYED))
def test_a_loop_whose_syntax_gives_no_count_keeps_its_order(
        compaction, monkeypatch, what):
    """Compacted as before and in the rising order: the program is the one
    the build makes with the analysis switched off, and the dense loop's
    arrays come out of it."""
    n, width = 2048, 512
    arrays = csr(n, 37, 0.4)
    # (a step, a bound: 1, and what the second kernel's lanes store there is
    # 1 too, so that no lane's bound depends on who ran first)
    arrays[6] = np.ones(n, np.int32)
    _dense, (info,) = both(compaction, UNKEYED[what], ("k",), arrays, n, width)
    assert info.compact[:2] + info.compact[4:] == (1, width, 0)
    assert lowering_meta([info])["compact"].endswith(";ordered:0")

    def text():
        fn, _info = KernelProgram(UNKEYED[what]).launcher("k", n, LOCAL, n,
                                                          platform="cpu")
        return str(fn.trace(0, tuple(jax.numpy.asarray(a) for a in arrays),
                            ()).jaxpr)

    built = text()
    monkeypatch.setattr(codegen, "_common_walks", lambda *a: {})
    assert built == text()


def test_a_loop_with_a_break_takes_the_key_as_a_bound(compaction):
    """A lane that breaks makes fewer passes than its key says; the order is
    free, so the key may err (``break and continue`` above is held to the
    dense loop and the oracle in both orders)."""
    compaction(512, 1.0)
    _out, (info,) = launch(KERNELS["break and continue"], ("k",),
                           csr(2048, 5, 0.6), 2048)
    assert info.compact[4] == 1 and info.loops_peeled == 0


def test_chunks_by_trip_count_make_fewer_passes(compaction, monkeypatch):
    """Lists of 1 to 40 entries: a chunk's loop runs until its longest list
    is through (and one trailing pass), so the passes of a launch are the sum
    over its chunks of the longest list + 1, from the lanes each chunk was
    HANDED (a host callback a chunk) and the lists' lengths.  A count of
    work, no time."""
    n, width = 8192, 512
    rng = np.random.default_rng(11)
    cnt = rng.integers(1, 41, n).astype(np.int32)
    lo = (np.cumsum(cnt) - cnt).astype(np.int32)
    arrays = [lo, cnt, rng.integers(0, n, int(cnt.sum())).astype(np.int32),
              rng.integers(0, 100, n).astype(np.int32),
              (rng.random(n) < 0.5).astype(np.int32), np.zeros(n, np.int32),
              np.zeros(n, np.int32)]
    on = arrays[4].astype(bool)
    compaction(width, 1.0)
    passes, outs = {}, {}
    for order in ORDERS:
        with monkeypatch.context() as mp:
            _seen, chunks = spy_on_the_lists(mp)
            set_order(mp, order)
            outs[order], _ = launch(KERNELS["local read after the loop"],
                                    ("k",), arrays, n)
            jax.effects_barrier()
        assert len(chunks) == -(-on.sum() // width)
        np.testing.assert_array_equal(np.sort(np.concatenate(chunks)),
                                      np.flatnonzero(on))
        passes[order] = sum(int(cnt[c].max()) + 1 for c in chunks)
    np.testing.assert_array_equal(outs["keyed"][5], outs["rising"][5])
    # 8 chunks of 512 lanes: 8 x 41 in the rising order, about half by count
    assert passes["rising"] == 8 * 41
    assert passes["keyed"] < 0.65 * passes["rising"]
