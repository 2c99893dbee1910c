"""Lane compaction of the vectorized-XLA lowering's masked loop (PR 41).

A masked loop entered under a lane-varying mask, whose passes gather or
scatter, runs over the lanes that ENTER it: their numbers are put in order
once and the loop walks them in chunks of ``codegen._COMPACT_WIDTH``
(``_exec_compacted``); with most lanes entering it runs over all of them as
it always did, and the count decides at run time.  Compaction is another
route to the SAME arrays: every case here is held bit for bit to the build
with the mechanism switched off (a chunk wider than any launch), on the CPU
rig; nothing here yields a device number.
"""

import importlib.util
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from cekirdekler_tpu.kernel import codegen  # noqa: E402
from cekirdekler_tpu.kernel.registry import KernelProgram, lowering_meta  # noqa: E402

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


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("share,lanes", [
    (0.0, 0), ("one lane", 1), (0.001, 8), (0.1, 819), (0.49, 4014),
    (1.0, NODES)])
@pytest.mark.parametrize("dense_share", [0.5, 1.0])
def test_a_bfs_level_is_the_same_to_the_last_bit(compaction, graph, share,
                                                 lanes, dense_share, platform):
    """One level (``BFS_1`` then ``BFS_2``) from a frontier of ``lanes``
    seeded nodes of one launch: the five state arrays of the compacting
    build against the dense one's, with the count choosing the path
    (``dense_share`` 0.5: a frontier of 49 % runs compacted, one of 100 %
    over all lanes) and with every frontier compacted (1.0)."""
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
    assert one.compact == (1, WIDTH, 3, 0) and two.compact == ()
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


@pytest.mark.parametrize("what", sorted(KERNELS))
# the last chunk shorter than W (700 lanes of 2048 on: chunks of 512); a
# range that is no multiple of W (1280 = 2.5 W) with a last chunk of its own
@pytest.mark.parametrize("n,width,share", [(2048, 512, 0.34), (1280, 512, 0.6)])
def test_a_compacted_loop_leaves_what_the_dense_one_leaves(
        compaction, what, n, width, share):
    arrays = csr(n, 23, share)
    on = arrays[4].astype(bool)
    assert on.sum() % width and n > width
    dense, infos = both(compaction, KERNELS[what], ("k",), arrays, n, width)
    (info,) = infos
    assert info.compact[:2] == (1, width)
    assert "compact" in lowering_meta(infos)
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
