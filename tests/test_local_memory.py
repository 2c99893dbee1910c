"""Work-group cooperation through ``compute()`` on the CPU rig: ``__local``
arrays, ``barrier()`` in group-uniform flow, a tree / a scan / a reversal in
the tile (ISSUE 45).  The system's normal path (``KernelProgram`` /
``compute()``) against plain numpy on seeded data; small integers as float32,
so every comparison is exact.  Nothing here yields a device number.

SHOC's ``reduce`` is the benchmark's own file (``benchmark/configs/
shoc_reduction.cl``) and is held to the configuration's plain reference
(``shoc_reduction_ref.partials``), which imports nothing of the program.
"""

import hashlib
import importlib.util
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from cekirdekler_tpu import ClArray  # noqa: E402
from cekirdekler_tpu.analysis import flag_row, summarize_kernel  # noqa: E402
from cekirdekler_tpu.arrays.clarray import ComputeValidationError  # noqa: E402
from cekirdekler_tpu.core.cruncher import NumberCruncher  # noqa: E402
from cekirdekler_tpu.core.worker import launch_ladder  # noqa: E402
from cekirdekler_tpu.errors import KernelLanguageError  # noqa: E402
from cekirdekler_tpu.hardware import platforms  # noqa: E402
from cekirdekler_tpu.kernel import codegen, lang  # noqa: E402
from cekirdekler_tpu.kernel.registry import KernelProgram, lowering_meta  # noqa: E402
from cekirdekler_tpu.trace.spans import TRACER  # noqa: E402

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs")


def source(name: str) -> str:
    with open(os.path.join(CONFIGS, name), encoding="utf-8") as f:
        return f.read()


REDUCE = source("shoc_reduction.cl")
_spec = importlib.util.spec_from_file_location(
    "shoc_reduction_ref", os.path.join(CONFIGS, "shoc_reduction_ref.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# reverse inside the group: the row fallback (the index runs against tid)
REVERSE = """
__kernel void reverse(__global const float* a, __global float* b) {
    __local float t[256];
    int tid = get_local_id(0);
    int gid = get_global_id(0);
    t[tid] = a[gid];
    barrier(CLK_LOCAL_MEM_FENCE);
    b[gid] = t[get_local_size(0) - 1 - tid];
}"""

# a Hillis-Steele scan in the tile: a uniform loop with two barriers a pass
SCAN = """
#define TILE 256
__kernel void scan(__global const float* a, __global float* b) {
    __local float t[TILE];
    int tid = get_local_id(0);
    int gid = get_global_id(0);
    t[tid] = a[gid];
    barrier(CLK_LOCAL_MEM_FENCE);
    for (int d = 1; d < get_local_size(0); d <<= 1) {
        float v = t[tid];
        if (tid >= d) { v += t[tid - d]; }
        barrier(CLK_LOCAL_MEM_FENCE);
        t[tid] = v;
        barrier(CLK_LOCAL_MEM_FENCE);
    }
    b[gid] = t[tid];
}"""

# a group's own offset into the tile, a broadcast of one element a group, a
# store that one work item a group passes, a barrier under a group-uniform if
GROUPWISE = """
__kernel void groupwise(__global const float* a, __global float* b, int n) {
    __local float t[64];
    __local float top[2];
    int tid = get_local_id(0);
    int grp = get_group_id(0);
    int gid = get_global_id(0);
    t[tid] = a[gid];
    if (grp < n) {
        barrier(CLK_LOCAL_MEM_FENCE | CLK_GLOBAL_MEM_FENCE);
        if (tid == 3) { top[1] = t[tid + grp]; }
        work_group_barrier(CLK_LOCAL_MEM_FENCE);
    }
    b[gid] = top[1] + t[grp];
}"""


@pytest.fixture(scope="module")
def devs():
    return platforms().cpus()


def small_ints(n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 3, n).astype(np.float32)


def run(devs, lanes: int, src: str, kernel: str, arrays: list, n: int,
        local: int, values=(), flags=()):
    """One synchronous ``compute()``; returns the host arrays."""
    bound = [ClArray(a, name=f"p{k}", **(flags[k] if flags else {}))
             for k, a in enumerate(arrays)]
    cr = NumberCruncher(devs.subset(lanes), src)
    try:
        first, *rest = bound
        first.next_param(*rest).compute(cr, 45, kernel, n, local,
                                        values=tuple(values))
        assert cr.number_of_errors_happened == 0
        return [np.array(b.host()) for b in bound]
    finally:
        cr.dispose()


# -- SHOC's reduce ----------------------------------------------------------

@pytest.mark.parametrize("groups", [1, 3, 64])
@pytest.mark.parametrize("local", [64, 128, 256])
def test_shoc_reduce_partials_are_the_references(devs, local, groups):
    grid = 2 * local * groups
    x = small_ints(5 * grid, seed=local + groups)
    flags = ({"read_only": True},
             {"read": False, "write": True, "write_all": True})
    for n in (5 * grid, 3 * grid, 4 * grid - 2 * local - 7, 1):
        got = run(devs, 1, REDUCE, "reduce",
                  [x, np.full(groups, -1, np.float32)], groups * local, local,
                  values=(n,), flags=flags)[1]
        want = ref.partials(x, n, groups, local)
        np.testing.assert_array_equal(got.astype(np.float64), want)
        assert want.sum() == x[:n].sum(dtype=np.float64) or n % (2 * local)


def test_the_reference_names_each_groups_elements():
    """Group ``g`` takes elements ``[2 L g, 2 L (g + 1))`` of every pass of
    the grid; a pass cut by ``n`` counts ``x[i] + x[i + L]`` while ``i < n``."""
    x = np.arange(48, dtype=np.float32)
    np.testing.assert_array_equal(ref.partials(x, 48, 3, 4), [
        x[0:8].sum() + x[24:32].sum(), x[8:16].sum() + x[32:40].sum(),
        x[16:24].sum() + x[40:48].sum()])
    cut = ref.partials(x, 26, 3, 4)  # the second pass: i = 24, 25 alone
    np.testing.assert_array_equal(cut, [
        x[0:8].sum() + x[24] + x[28] + x[25] + x[29], x[8:16].sum(),
        x[16:24].sum()])
    np.testing.assert_array_equal(
        ref.partials(x, 48, 3, 4, first_pass=1),
        ref.partials(x, 48, 3, 4) - ref.partials(x, 24, 3, 4))


def test_reduce_lowers_by_shifts_and_one_broadcast():
    prog = KernelProgram(REDUCE)
    fn, info = prog.launcher("reduce", 4 * 256, 256, 4 * 256)
    x = small_ints(8 * 256 * 3, 5)
    out = fn(0, (jnp.asarray(x), jnp.full(4, -1, jnp.float32)), (x.size,))
    np.testing.assert_array_equal(np.asarray(out[1], np.float64),
                                  ref.partials(x, x.size, 4, 256))
    assert info.local == (1, 1024, 2)
    assert info.local_sites == {"shift": 6, "uniform": 1, "row": 0}
    assert (info.loops_counted, info.loops_masked) == (1, 1)
    meta = lowering_meta([info])
    assert meta["local"] == ("arrays:1;bytes:1024;barriers:2;"
                             "sites:shift:6,uniform:1,row:0")
    assert meta["access"].endswith(
        ";gather:0;scatter:1;carried:0;local:7;group:2;settled:2")
    # the groups' windows lie 512 apart: the launcher holds the one-slice read
    text = str(fn.trace(0, (jnp.asarray(x), jnp.zeros(4, jnp.float32)),
                        (x.size,)).jaxpr)
    assert "f32[12,4,128]" in text and "f32[4,2,128]" in text  # chunks of lanes would tear groups apart


# -- the group slice: a read at ``local id + (the same in a group)`` ---------
# (ISSUE 46)  Every case runs three ways: the build (which must count the
# reads it names as ``group``), the same kernel built with the form switched
# off (the gather it replaces: byte for byte), and the scalar oracle.

def _tiled(body: str) -> str:
    """A kernel with a tile (so its launches are whole groups) around
    ``body``, which leaves ``float v``."""
    return """
    __kernel void k(__global const float* x, __global const int* offs,
                    __global float* out, int n, int c) {
        __local float t[256];
        int tid = get_local_id(0);
        int gid = get_global_id(0);
        t[tid] = 1.0f;
        float v = 0.0f;
        %s
        out[gid] = v + t[tid];
    }""" % body


WALK = """
        int i = get_group_id(0) * (get_local_size(0) * 2) + tid;
        int grid = get_local_size(0) * 2 * get_num_groups(0);
        int bs = get_local_size(0);
        while (i < n) { v += x[i] + x[i + bs]; i += grid; }"""

# name -> (body, groups, local, elements of x, n, c, reads lowered as group)
GROUP_SLICE = {
    "a walk, n whole passes": (WALK, 3, 64, 3 * 128 * 4, 3 * 128 * 4, 0, 2),
    "a walk, n cuts a group in the middle": (WALK, 3, 64, 2000, 3 * 128 + 91, 0, 2),
    "a walk, n 0": (WALK, 3, 64, 512, 0, 0, 2),
    "a walk, n 1": (WALK, 3, 64, 512, 1, 0, 2),
    "a walk, n past the buffer's end": (WALK, 3, 64, 3 * 128 + 70, 1000, 0, 2),
    "a walk, a buffer shorter than a group": (WALK, 1, 64, 40, 100, 0, 2),
    "a walk, 1 group of 256": (WALK, 1, 256, 3000, 2777, 0, 2),
    "a walk, 64 groups of 128": (WALK, 64, 128, 64 * 256 * 2 + 300,
                                 64 * 256 * 2 + 130, 0, 2),
    "a walk, 3 groups of 256": (WALK, 3, 256, 5000, 4321, 0, 2),
    # a pitch of whole rows, a buffer of whole pitches: the windows are one
    # 2-D slice where the pass's starts allow it, and a window a group where
    # they do not (a start off the rows, behind the end, apart by another
    # distance than the hint's)
    "one slice a pass": (WALK, 3, 256, 8 * 512 * 3, 8 * 512 * 3 - 300, 0, 2),
    "one slice a pass, 64 groups of 128": (WALK, 64, 128, 64 * 256 * 3,
                                           64 * 256 * 3, 0, 2),
    "one slice, then passes behind the end": (WALK, 3, 128, 256 * 3 * 2,
                                              256 * 3 * 2 + 900, 0, 2),
    "a pitch, starts off the rows": (
        WALK.replace("+ tid;", "+ tid + c;"), 3, 128, 256 * 3 * 4, 2500, 5, 2),
    "a pitch, starts before the buffer": (
        WALK.replace("+ tid;", "+ tid - c;"), 3, 128, 256 * 3 * 4, 2500, 256, 2),
    "a pitch that one group leaves": (
        WALK.replace("int grid", "if (get_group_id(0) == 1) { i += 128; }\n"
                     "        int grid"), 3, 128, 256 * 3 * 4, 3000, 0, 2),
    "a pitch, groups that sit out": (
        "if (get_group_id(0) != 0) {" + WALK + "}", 3, 128, 256 * 3 * 4,
        3000, 0, 2),
    "a pitch under a per-lane if": (
        "if (tid % 5 != 0) {" + WALK + "}", 3, 128, 256 * 3 * 4, 2900, 0, 2),
    "a negative group offset": (
        "v = x[tid + get_group_id(0) * 64 - c];", 4, 64, 300, 0, 100, 1),
    "u from a buffer at the group's id": (
        "v = x[offs[get_group_id(0)] + tid];", 5, 64, 700, 0, 0, 1),
    "a launch-uniform runtime term": ("v = x[tid + c] + x[c + n + tid];",
                                      3, 128, 500, 77, 13, 2),
    "tid under integer casts": (
        "v = x[(int)get_local_id(0) + (int)(get_group_id(0)) * 7]"
        " + x[(unsigned int)tid + c];", 3, 64, 300, 0, 5, 2),
    "updates by = + and -= and ++": ("""
        int i = tid + c;
        for (int k = 0; k < 3; k++) { v += x[i]; i = i + 5; }
        i -= 40; v += x[i]; i++; v += x[i - get_group_id(0)];""",
                                     3, 64, 256, 0, 9, 3),
    "a walker off another walker": ("""
        int i = get_group_id(0) * 32 + tid;
        int j = i + c;
        v = x[j] + x[j + n];""", 3, 64, 256, 11, 3, 2),
    "under a group-uniform if": (
        "if (get_group_id(0) % 2 == 0) { v = x[tid + c]; }", 4, 64, 256, 0, 3, 1),
    "under a per-lane if": (
        "if (tid >= c) { v = x[tid + get_group_id(0) * 3]; }", 4, 64, 256, 0, 17, 1),
    "a walk under a per-lane if": ("""
        if (tid % 3 != 1) {
            int i = tid + get_group_id(0) * c;
            while (i < n) { v += x[i]; i += 100; }
        }""", 3, 64, 700, 520, 70, 1),
    "a walk whose step stands in a for": ("""
        for (int i = tid + c; i < n + get_group_id(0); i += 64) { v += x[i + 1]; }
        """, 3, 64, 400, 333, 2, 1),
}

# what must KEEP the gather: name -> (body, gathers at least)
KEEP_GATHER = {
    "2 * tid + u": ("v = x[2 * tid + c];", 1),
    "u - tid": ("v = x[c + 200 - tid];", 1),
    "tid + a lane-varying term": ("v = x[tid + offs[gid]];", 1),
    "two local ids": ("v = x[tid + tid + c];", 1),
    "a walker also assigned a lane-varying value": ("""
        int i = tid + c;
        if (tid == 3) { i = offs[gid]; }
        v = x[i];""", 1),
    "a walker multiplied": ("int i = tid + c; i *= 2; v = x[i];", 1),
    "a walker read behind the loop that moved it": ("""
        int i = tid + c;
        while (i < n + 9 * (tid % 4)) { i += 64; }
        v = x[i];""", 1),
    "a walker moved under a per-lane if": ("""
        int i = tid + c;
        if (tid % 2 == 0) { i += 7; }
        v = x[i];""", 1),
    "a walker moved under a per-lane if in a loop": ("""
        int i = tid + c;
        for (int k = 0; k < 4; k++) {
            if ((tid + k) % 3 == 0) { i += 5; v += x[i]; }
        }""", 1),
    "a walker moved behind a continue": ("""
        int i = tid + c;
        int k = 0;
        while (k < 4 + tid % 2) {
            k++;
            if ((tid + k) % 3 == 0) { continue; }
            i += 5; v += x[i];
        }""", 1),
    "a walker of an inner loop the lanes leave apart": ("""
        for (int r = 0; r < 2; r++) {
            int i = tid + c;
            int k = 0;
            while (k < tid % 3) { k++; i += 4; }
            while (k < 5) { k++; v += x[i]; i += 4; }
        }""", 1),
    "a walker read by the condition of its loop": ("""
        int i = tid + c;
        while (x[i] < 40.0f + tid) { i += 64; v += 1.0f; }""", 1),
    # a value parameter starts from what the caller gave it: a move by a
    # group-uniform amount is no declaration of ``local id + u``
    "a value parameter moved under a per-lane if": (
        "if (tid % 2 == 1) { c += 5; v = x[c]; }", 1),
    "a value parameter moved in a loop the lanes leave apart": (
        "while (c < n + tid) { v += x[c]; c += 5; }", 1),
    "a value parameter moved and read behind the loop": ("""
        for (int k = 0; k < 2 + tid % 3; k++) { c = c + 7; }
        v = x[c] + x[c + tid];""", 2),
    "a value parameter set to the form under a per-lane if": (
        "if (tid % 2 == 1) { c = tid + 9; } v = x[c];", 1),
    # a float between the local id and the index rounds lane by lane:
    # work items 3 and 4 of ``tid - 3.5f`` both land on 0
    "a walker declared from a float": ("int i = tid - 3.5f; v = x[i + 40];", 1),
    "a walker moved by a float": (
        "int i = tid - 7; i += 3.5f; v = x[i + 40];", 1),
    "a walker off a float local": (
        "float f = c + 0.5f; int i = tid - f - 3; v = x[i + 40];", 1),
    "a short walker": ("short i = tid + c; v = x[i];", 1),
    "the local id under a narrow cast": ("v = x[(char)tid + c];", 1),
    "a local id kept in a char": (
        "char t8 = get_local_id(0); v = x[t8 + c];", 1),
}


def _group_arrays(size: int, elems: int, seed: int) -> tuple:
    """``(x, offs, the launch's device arrays)`` of a group-read case."""
    rng = np.random.default_rng(seed)
    x = (rng.integers(0, 1000, elems) + np.arange(elems) * 1024).astype(np.float32)
    offs = rng.integers(-80, elems + 80, max(size, 8)).astype(np.int32)
    return x, offs, (jnp.asarray(x), jnp.asarray(offs),
                     jnp.zeros(size, jnp.float32))


def _group_case(src: str, groups: int, local: int, elems: int, n: int, c: int,
                seed: int, monkeypatch):
    """``(info, out)`` of the build, with the gather's and the oracle's
    outputs held equal to it."""
    from tests.kernel_oracle import Oracle

    size = groups * local
    x, offs, arrays = _group_arrays(size, elems, seed)
    fn, info = KernelProgram(src).launcher("k", size, local, size)
    out = np.asarray(fn(0, arrays, (n, c))[2])
    for switched_off in ("_group_sites", "_settled_walks"):
        # the gather it replaces, and the check made pass by pass (ISSUE 47)
        with monkeypatch.context() as mp:
            mp.setattr(codegen, switched_off, lambda *a: {})
            ref_fn, ref_info = KernelProgram(src).launcher("k", size, local, size)
            want = np.asarray(ref_fn(0, arrays, (n, c))[2])
        assert out.tobytes() == want.tobytes(), switched_off
        if switched_off == "_settled_walks":
            assert ref_info.access["group"] == info.access["group"]
            assert ref_info.access["settled"] == 0
            continue
        gather_info = ref_info
        assert ref_info.access["group"] == 0
    host = {"x": x.copy(), "offs": offs.copy(), "out": np.zeros(size, np.float32)}
    kdef, = lang.parse_kernels(src)
    Oracle(kdef, local_size=local).run(host, {"n": n, "c": c}, size)
    np.testing.assert_array_equal(out, host["out"])
    return info, gather_info


@pytest.mark.parametrize("case", sorted(GROUP_SLICE))
def test_a_read_at_local_id_plus_a_group_value_is_one_window_a_group(
        case, monkeypatch):
    body, groups, local, elems, n, c, sites = GROUP_SLICE[case]
    info, ref_info = _group_case(_tiled(body), groups, local, elems, n, c,
                                 len(case), monkeypatch)
    # (the read of ``offs`` at the group's id is itself a gather, and stays)
    inner = int("offs[" in body)
    assert info.access["group"] == sites and info.access["gather"] == inner
    assert ref_info.access["gather"] == sites + inner
    assert f";local:2;group:{sites};settled:" in lowering_meta([info])["access"]


@pytest.mark.parametrize("case", sorted(KEEP_GATHER))
def test_what_is_not_that_form_keeps_the_gather(case, monkeypatch):
    body, gathers = KEEP_GATHER[case]
    info, _ = _group_case(_tiled(body), 3, 64, 400, 300, 21, len(case),
                          monkeypatch)
    assert info.access["group"] == 0 and info.access["gather"] >= gathers


# -- the windows settled once a loop (ISSUE 47) ------------------------------
# A group read whose index moves, pass by pass of its loop, by a step the
# build knows that is a whole multiple of its pitch: the starts are checked
# once, before the loop, and the passes inside the buffer hold no check.
# Every case runs four ways (_group_case): this build, the build that checks
# pass by pass, the gather, the scalar oracle.  ``whole``: every pass that
# reads anything is a settled one, shown by running the build once more with
# the per-pass read poisoned.

PITCHED = """
        int i = get_group_id(0) * (get_local_size(0) * 2) + tid + c;
        int grid = get_local_size(0) * 2 * get_num_groups(0);
        int bs = get_local_size(0);
        while (i < n) { v += x[i] + x[i + bs]; %s }"""
SHOC = PITCHED % "i += grid;"
FULL = 256 * 3 * 4  # four passes of three groups of 128 at SHOC's pitch

# name -> (body, groups, local, elements of x, n, c, group reads, of them
#          settled, whole)
SETTLED = {
    "SHOC's walk, every pass inside": (SHOC, 3, 128, FULL, FULL - 100, 0, 2, 2, True),
    "SHOC's walk, 8 groups of 256": (SHOC, 8, 256, 512 * 8 * 3, 512 * 8 * 3, 0,
                                     2, 2, True),
    "a step that is no multiple of the pitch": (
        PITCHED % "i += grid + 128;", 3, 128, FULL * 2, FULL * 2 - 77, 0, 2, 0, False),
    "a step read at the group's id": (
        PITCHED % "i += grid + 0 * offs[get_group_id(0)];", 3, 128, FULL,
        FULL - 9, 0, 2, 0, False),
    "a step the group's id has a part in": (
        PITCHED % "i += grid; i += 256 * (get_group_id(0) / 8);", 3, 128, FULL,
        FULL, 0, 2, 0, False),
    "a walker moved under an if": (
        PITCHED % "if (c < 1) { i += grid; } else { i += 2 * grid; }", 3, 128,
        FULL, FULL, 0, 2, 0, False),
    "a second local the loop moves": ("""
        int k = 0;""" + PITCHED.replace("x[i + bs]", "x[i + k]")
        % "i += grid; k += 256;", 3, 128, FULL * 2, FULL, 0, 1, 1, False),
    "the last passes over the end": (SHOC, 4, 128, 256 * 4 * 3,
                                     256 * 4 * 3 + 2000, 0, 2, 2, False),
    "a buffer one pitch short of the walk": (SHOC, 3, 128, FULL - 256, FULL, 0,
                                             2, 2, False),
    "a first pass off a row": (SHOC, 3, 128, FULL, FULL - 300, 64, 2, 2, False),
    "a first pass before the buffer": (SHOC, 3, 128, FULL, FULL, -512, 2, 2, False),
    "a loop some groups enter": (
        "if (get_group_id(0) != 1) {" + SHOC + "}", 3, 128, FULL, FULL, 0, 2, 2, True),
    "a loop group 0 stays out of": (
        "if (get_group_id(0) > 0 && tid % 7 != 2) {" + SHOC + "}", 4, 128,
        256 * 4 * 3, 256 * 4 * 3 - 5, 0, 2, 2, True),
    "a loop no lane enters": (SHOC, 3, 128, FULL, 0, 0, 2, 2, True),
    "a step of 0": ("""
        int i = get_group_id(0) * 256 + tid;
        int k = 0;
        while (k < 2 + tid % 3) { v += x[i] + x[i + c]; k++; }""",
                    3, 128, 256 * 4, 0, 128, 2, 2, True),
    "a negative step": ("""
        int i = get_group_id(0) * 256 + tid + c;
        int grid = 256 * get_num_groups(0);
        while (i >= n) { v += x[i]; i -= grid; }""",
                        3, 128, FULL, 40, 256 * 9, 1, 1, True),
    "a read ahead of the step and one behind it": (
        PITCHED.replace("+ x[i + bs]", "") % "i += grid; v += x[i - grid + bs];",
        3, 128, FULL, FULL - 200, 0, 2, 2, True),
    "a for whose init declares the walker": ("""
        int grid = 256 * get_num_groups(0);
        for (int i = get_group_id(0) * 256 + tid; i < n; i += grid) {
            v += x[i + 128];
        }""", 3, 128, FULL, FULL - 130, 0, 1, 1, True),
    "lanes that break": (
        PITCHED.replace("{ v +=", "{ if (tid == 3 && i > 900) { break; } v +=")
        % "i += grid;", 3, 128, FULL, FULL, 0, 2, 2, True),
}


@pytest.mark.parametrize("case", sorted(SETTLED))
def test_a_walk_by_a_step_the_build_knows_settles_its_windows_once(
        case, monkeypatch):
    body, groups, local, elems, n, c, sites, settled, whole = SETTLED[case]
    info, _ = _group_case(_tiled(body), groups, local, elems, n, c, len(case),
                          monkeypatch)
    assert (info.access["group"], info.access["settled"]) == (sites, settled)
    assert lowering_meta([info])["access"].endswith(
        f";local:2;group:{sites};settled:{settled}")
    if not whole:
        return
    # no pass that reads anything takes the read that is checked pass by pass
    size = groups * local
    _x, _offs, arrays = _group_arrays(size, elems, len(case))
    fn, _info = KernelProgram(_tiled(body)).launcher("k", size, local, size)
    want = np.asarray(fn(0, arrays, (n, c))[2])
    monkeypatch.setattr(
        codegen, "_group_slice", lambda ctx, name, idx, pitch=0: jnp.full(
            ctx.shape, jnp.nan, ctx.bufs[name].dtype))
    fn, _info = KernelProgram(_tiled(body)).launcher("k", size, local, size)
    np.testing.assert_array_equal(np.asarray(fn(0, arrays, (n, c))[2]), want)


def _eqns(jaxpr, name: str) -> list:
    """Every equation of primitive ``name`` under ``jaxpr``, inner ones too."""
    found = []
    for eqn in jaxpr.eqns:
        found += [eqn] * (eqn.primitive.name == name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _eqns(sub, name)
    return found


def test_the_passes_reduce_takes_in_its_cell_hold_no_cond():
    """SHOC's launcher as the cell builds it (64 groups of 256 over 2^26
    elements, by shape alone): behind the common passes (ISSUE 51:
    tests/test_peeled_loops.py has that ``while``) the walk is two
    ``while``s, the first with the two one-slice reads and no ``cond`` in its
    body or its condition, the second the loop as it was (two ``cond``s a
    pass)."""
    fn, info = KernelProgram(REDUCE).launcher("reduce", 16384, 256, 16384,
                                              platform="tpu")
    jaxpr = fn.trace(0, (jax.ShapeDtypeStruct((1 << 26,), jnp.float32),
                         jax.ShapeDtypeStruct((64,), jnp.float32)),
                     (np.uint32(1 << 26),)).jaxpr.jaxpr
    walks = [e for e in jaxpr.eqns if e.primitive.name == "while"
             and _eqns(e.params["body_jaxpr"].jaxpr, "dynamic_slice")
             and any(v.aval.shape == (64, 2, 128) for s in _eqns(
                 e.params["body_jaxpr"].jaxpr, "dynamic_slice") for v in s.outvars)]
    assert len(walks) == 3 and info.access["settled"] == 2
    walks = walks[1:]
    settled, checked = (e.params["body_jaxpr"].jaxpr for e in walks)
    assert not _eqns(settled, "cond") and not _eqns(settled, "gather")
    assert not _eqns(walks[0].params["cond_jaxpr"].jaxpr, "cond")
    assert len([s for s in _eqns(settled, "dynamic_slice")
                if s.outvars[0].aval.shape == (64, 2, 128)]) == 2
    # the loop as PR 46 built it: each read picks its fetch pass by pass
    assert len([c for c in checked.eqns if c.primitive.name == "cond"]) == 2
    # the settled passes reduce nothing over the lanes but the mask's `any`
    assert [r.primitive.name for r in settled.eqns
            if r.primitive.name.startswith("reduce")] == []


@pytest.mark.parametrize("tile", ["none", "a barrier alone"])
def test_a_kernel_without_a_tile_keeps_the_gather(tile):
    """Only a kernel with a ``__local`` array is promised launches of whole
    groups: without one the walk is the gather it was."""
    src = """
    __kernel void k(__global const float* x, __global float* out, int n) {
        int tid = get_local_id(0);
        int i = get_group_id(0) * 64 + tid;
        float v = 0.0f;
        %s
        while (i < n) { v += x[i]; i += 192; }
        out[get_global_id(0)] = v;
    }""" % ("barrier(CLK_GLOBAL_MEM_FENCE);" if tile != "none" else "")
    x = small_ints(500, 3) + np.arange(500, dtype=np.float32)
    fn, info = KernelProgram(src).launcher("k", 192, 64, 192)
    out = np.asarray(fn(0, (jnp.asarray(x), jnp.zeros(192, jnp.float32)), (500,))[1])
    np.testing.assert_array_equal(
        out, [x[i::192].sum(dtype=np.float32) for i in range(192)])
    assert info.access["gather"] == 1 and info.access.get("group", 0) == 0
    assert "group" not in lowering_meta([info])["access"] or tile != "none"


# -- the row fallback, a scan, group-uniform offsets ------------------------

@pytest.mark.parametrize("local", [64, 256])
def test_reverse_inside_the_group_is_the_row_fallback(devs, local):
    a = small_ints(6 * local, 11) + np.arange(6 * local, dtype=np.float32)
    got = run(devs, 1, REVERSE, "reverse", [a, np.zeros_like(a)],
              a.size, local)[1]
    np.testing.assert_array_equal(got, a.reshape(-1, local)[:, ::-1].ravel())
    _fn, info = KernelProgram(REVERSE).launcher("reverse", local, local, local)
    _fn(0, (jnp.asarray(a), jnp.asarray(a)))
    assert info.local_sites == {"shift": 1, "uniform": 0, "row": 1}


@pytest.mark.parametrize("local", [32, 256])
def test_hillis_steele_scan_in_the_tile(devs, local):
    a = small_ints(5 * local, 13)
    got = run(devs, 1, SCAN, "scan", [a, np.zeros_like(a)], a.size, local)[1]
    np.testing.assert_array_equal(
        got, np.cumsum(a.reshape(-1, local), axis=1).ravel())
    _fn, info = KernelProgram(SCAN).launcher("scan", local, local, local)
    assert info.local == (1, 1024, 3)
    assert (info.loops_counted, info.loops_masked) == (1, 0)


@pytest.mark.parametrize("n", [0, 2, 5])
def test_group_uniform_offsets_and_a_barrier_under_a_group_uniform_if(devs, n):
    local, groups = 32, 5
    a = small_ints(groups * local, 17) + np.arange(groups * local,
                                                   dtype=np.float32)
    got = run(devs, 1, GROUPWISE, "groupwise", [a, np.zeros_like(a)], a.size,
              local, values=(n,))[1]
    rows = a.reshape(groups, local)
    grp = np.arange(groups)
    top = np.where(grp < n, rows[grp, 3 + grp], 0.0)  # the tile starts at zero
    want = (top + rows[grp, grp])[:, None] * np.ones((1, local), np.float32)
    np.testing.assert_array_equal(got, want.ravel())


def test_a_tile_shorter_and_longer_than_the_group():
    """``K`` is the declaration's, whatever the local range: a store beyond
    the row is dropped, a load beyond it reads the nearest element."""
    src = """
    __kernel void k(__global const float* a, __global float* b) {
        __local float t[8];
        int tid = get_local_id(0);
        t[tid] = a[get_global_id(0)];
        barrier(CLK_LOCAL_MEM_FENCE);
        b[get_global_id(0)] = t[tid + 1];
    }"""
    a = np.arange(32, dtype=np.float32)
    fn, _ = KernelProgram(src).launcher("k", 32, 16, 32)  # L = 16 > K = 8
    got = np.asarray(fn(0, (jnp.asarray(a), jnp.zeros(32)))[1]).reshape(2, 16)
    rows = a.reshape(2, 16)
    np.testing.assert_array_equal(got[:, :7], rows[:, 1:8])
    np.testing.assert_array_equal(got[:, 7:], np.repeat(rows[:, 7:8], 9, 1))
    fn, _ = KernelProgram(src).launcher("k", 32, 4, 32)   # L = 4 < K = 8
    got = np.asarray(fn(0, (jnp.asarray(a), jnp.zeros(32)))[1]).reshape(8, 4)
    rows = a.reshape(8, 4)
    np.testing.assert_array_equal(got[:, :3], rows[:, 1:])
    assert (got[:, 3] == 0).all()  # element 4 of the tile: still zero


# -- groups stay whole: one launch, ladder rungs, two lanes -----------------

@pytest.mark.parametrize("src,kernel", [(REVERSE, "reverse"), (SCAN, "scan")])
def test_whole_as_rungs_and_over_two_lanes_give_the_same_bytes(
        devs, src, kernel):
    local, groups = 64, 7  # a ladder of 4 + 2 + 1 groups on one lane
    a = small_ints(groups * local, 19) + np.arange(groups * local,
                                                   dtype=np.float32)
    assert launch_ladder(groups * local, local) == [4 * local, 2 * local, local]
    fn, _ = KernelProgram(src).launcher(kernel, a.size, local, a.size)
    whole = np.asarray(fn(0, (jnp.asarray(a), jnp.zeros_like(a)))[1])
    rungs = run(devs, 1, src, kernel, [a, np.zeros_like(a)], a.size, local)[1]
    flags = ({"partial_read": True}, {"read": False, "write": True})
    lanes = run(devs, 2, src, kernel, [a, np.zeros_like(a)], a.size, local,
                flags=flags)[1]
    assert whole.tobytes() == rungs.tobytes() == lanes.tobytes()


@pytest.mark.parametrize("size,step", [(7 * 256, 256), (64 * 256, 256),
                                       (1000 * 64, 64), (13 * 512, 512)])
def test_every_rung_of_the_ladder_is_whole_units_of_the_step(size, step):
    rungs = launch_ladder(size, step)
    assert sum(rungs) == size and all(r % step == 0 for r in rungs)


def test_a_launch_that_cuts_a_group_is_refused():
    prog = KernelProgram(REVERSE)
    with pytest.raises(KernelLanguageError, match="whole work-groups"):
        prog.launcher("reverse", 96, 64, 192)


def test_a_global_offset_inside_a_group_is_refused(devs):
    a = small_ints(256, 3)
    arrays = [ClArray(a, name="a"), ClArray(np.zeros_like(a), name="b")]
    cr = NumberCruncher(devs.subset(1), REVERSE)
    try:
        group = arrays[0].next_param(arrays[1])
        with pytest.raises(ComputeValidationError, match="whole work-groups"):
            group.compute(cr, 7, "reverse", 128, 64, global_offset=32)
        cr.reset_errors()
        group.compute(cr, 7, "reverse", 128, 64, global_offset=64)
        np.testing.assert_array_equal(
            arrays[1].host()[64:192], a[64:192].reshape(2, 64)[:, ::-1].ravel())
    finally:
        cr.dispose()


# -- spans, veto, verdict ----------------------------------------------------

def test_the_launch_and_compile_spans_carry_the_local_field(devs, monkeypatch):
    """The fields ride the spans' metadata (the profiler annotation): spied
    on where the tracer records them."""
    seen = []
    real = TRACER.record

    def record(kind, t0, *args, **meta):
        if kind in ("launch", "compile"):
            seen.append((kind, meta))
        return real(kind, t0, *args, **meta)

    monkeypatch.setattr(TRACER, "record", record)
    x = small_ints(2 * 64 * 4 * 2, 23)
    TRACER.enable(clear=True)
    try:
        run(devs, 1, REDUCE, "reduce", [x, np.full(4, -1, np.float32)],
            4 * 64, 64, values=(x.size,),
            flags=({"read_only": True},
                   {"read": False, "write": True, "write_all": True}))
    finally:
        TRACER.disable()
    assert {kind for kind, _meta in seen} == {"launch", "compile"}
    for _kind, meta in seen:
        assert meta["loops"] == "counted:1;masked:1;peeled:1"
        assert meta["local"] == ("arrays:1;bytes:1024;barriers:2;"
                                 "sites:shift:6,uniform:1,row:0")
        assert meta["access"] == ("slice:0;strided:0;uniform:0;gather:0;"
                                  "scatter:1;carried:0;local:7;group:2;"
                                  "settled:0")  # (groups of 64: no rows)


def test_a_tpu_lane_takes_the_xla_half_with_a_named_veto():
    prog = KernelProgram(REDUCE)
    _fn, info = prog.launcher("reduce", 256, 256, 256, platform="tpu")
    assert info.lowering == "xla" and info.veto.startswith("local-memory")
    assert prog.lowerings("reduce", "tpu") == {("xla", info.veto)}
    assert prog.cooperates("reduce")


def test_a_local_array_is_no_buffer_to_the_verifier():
    kdef, = lang.parse_kernels(REDUCE)
    summary = summarize_kernel(kdef)
    assert "sdata" not in summary.reads and "sdata" not in summary.writes
    assert set(summary.reads) == {"g_idata"}
    (write,) = summary.writes["g_odata"]
    assert write.av.coef is None  # at get_group_id(0): not affine in the id
    prog = KernelProgram(REDUCE)
    rows = (flag_row(ClArray(np.zeros(8, np.float32), read_only=True)),
            flag_row(ClArray(np.zeros(1, np.float32), read=False, write=True,
                             write_all=True)))
    assert not prog.verify(("reduce",), rows, lanes=1).errors
    kinds = {f.kind for f in prog.verify(("reduce",), rows).errors}
    assert "scatter-write" in kinds  # on more lanes: ROADMAP M12


# -- the control: a kernel without tile or barrier builds what it built -----

def _hlo(src_file: str, kernel: str, arrays: tuple, values: tuple = ()):
    prog = KernelProgram(source(src_file))
    fn, info = prog.launcher(kernel, 1024, 256, 4096)
    text = fn.trace(0, arrays, values).lower().as_text()
    return hashlib.sha256(text.encode()).hexdigest(), info


_F32, _I32, _I8 = ("float32", "int32", "int8")
_BFS = ((_I32,) * 3 + (_I8,) * 3 + (_I32, _I8), (np.int32(4000),))
# file, kernel -> the arrays' types, the values, the hash.  SpMV's was taken
# on a5e08b9 (PR 45's parent), the two of Rodinia's BFS on b9ce6ad (PR 47's:
# masked loops and ifs that pass ``_exec_masked``), with ``_hlo`` itself
UNTILED = {
    ("hpcg_spmv.cl", "spmv"): (
        (_I32, _I32, _F32, _F32, _F32), (np.float32(1.5),),
        "8548704244454052be315cdd13728139443dbb4d92d7997ff4f9187006ac1d5d"),
    ("rodinia_bfs.cl", "BFS_1"): _BFS + (
        "e34f29fa0fa12b4f24562ee1c15a3e6b2e6c7b9c7353a6c623ee26bd7979a9ab",),
    ("rodinia_bfs.cl", "BFS_2"): _BFS + (
        "be81ec125325b10ce7b145b7a468092e3e07f93010fa0db71951376ac2e6ac03",),
}


@pytest.mark.parametrize("src_file,kernel", sorted(UNTILED))
def test_a_kernel_with_no_tile_builds_the_hlo_it_built_before(src_file, kernel):
    """The hashes were taken on a parent commit with this very function: a
    kernel with neither a ``__local`` array nor a barrier lowers to the last
    operation as it did."""
    types, values, want = UNTILED[src_file, kernel]
    sha, info = _hlo(src_file, kernel, tuple(
        jax.ShapeDtypeStruct((4096,), jnp.dtype(t)) for t in types), values)
    assert info.local == () and info.local_sites == {}
    assert "local" not in lowering_meta([info])
    assert sha == want


def test_reduce_checked_pass_by_pass_is_the_launcher_it_was(monkeypatch):
    """What is left of a walk once its settled passes are over runs "the loop
    as PR 46 built it": with the settling switched off the launcher of SHOC's
    ``reduce`` is that commit's (b9ce6ad) to the last operation, by ``_hlo``
    there; with it on it is another program.  (Both without the common
    passes, which that commit did not peel: ISSUE 51.)"""
    arrays = (jax.ShapeDtypeStruct((4096,), jnp.float32),) * 2
    monkeypatch.setattr(codegen, "_common_walks", lambda *a: {})
    sha, info = _hlo("shoc_reduction.cl", "reduce", arrays, (np.uint32(4000),))
    assert sha != REDUCE_PER_PASS_SHA and info.access["settled"] == 2
    monkeypatch.setattr(codegen, "_settled_walks", lambda *a: {})
    sha, info = _hlo("shoc_reduction.cl", "reduce", arrays, (np.uint32(4000),))
    assert sha == REDUCE_PER_PASS_SHA and info.access["settled"] == 0


REDUCE_PER_PASS_SHA = "443cc4105f1b92139e9cc1a487cf00a55bf4256bbfb6ec4de9ec3b3a69144a90"


@pytest.mark.parametrize("name", ["nbody_direct.cl", "hpcg_spmv.cl",
                                  "rodinia_bfs.cl", "polybench_mvt.cl"])
def test_the_accepted_kernels_do_not_cooperate(name):
    for kdef in lang.parse_kernels(source(name)):
        assert codegen._cooperation(kdef) is None
        assert not KernelProgram(source(name)).cooperates(kdef.name)


# -- refusals ----------------------------------------------------------------

def _kernel(body: str, params: str = "__global float* x") -> str:
    return f"__kernel void k({params}) {{\n{body}\n}}"


REFUSED = {
    "a barrier under a per-lane if": (_kernel("""
        __local float t[64];
        int tid = get_local_id(0);
        if (tid < 3) {
            barrier(CLK_LOCAL_MEM_FENCE);
        }
        x[get_global_id(0)] = t[tid];"""), "^line 6: barrier-divergent.*`if` on line 5"),
    "a barrier in a loop with a per-lane bound": (_kernel("""
        int tid = get_local_id(0);
        for (int j = 0; j < tid; j++) {
            x[get_global_id(0)] += 1.0f;
            barrier(CLK_GLOBAL_MEM_FENCE);
        }"""), "^line 6: barrier-divergent.*loop on line 4"),
    "a barrier behind a per-lane break": (_kernel("""
        int tid = get_local_id(0);
        for (int j = 0; j < 4; j++) {
            if (x[get_global_id(0)] > 1.0f) break;
            barrier(CLK_LOCAL_MEM_FENCE);
        }"""), "^line 6: barrier-divergent.*loop on line 4"),
    "a barrier under a local assigned from the local id": (_kernel("""
        int pair = get_local_id(0) / 2;
        if (pair == 0) { barrier(CLK_LOCAL_MEM_FENCE); }"""),
        "^line 4: barrier-divergent.*`if` on line 4"),
    "a barrier in a kernel with an early return": (_kernel("""
        if (get_global_id(0) > 5) return;
        barrier(CLK_LOCAL_MEM_FENCE);"""), "^line 4: barrier-divergent.*early `return`"),
    "a __local parameter": (_kernel(
        "x[0] = t[0];", "__global float* x, __local float* t"),
        "declare it inside the kernel"),
    "an atomic": (_kernel("atomic_add(x, 1);"), "atomics are not supported"),
    "a memory fence": (_kernel("mem_fence(CLK_LOCAL_MEM_FENCE);"),
                       "mem_fence"),
    "a barrier as a value": (_kernel("int b = barrier(0);"),
                             "statement of its own"),
    "a __local pointer": (_kernel("__local float* p;"), "through a pointer"),
    "a __local scalar": (_kernel("__local float s;"), "must be an array"),
    "a __local array in a branch": (_kernel("""
        if (get_group_id(0) == 0) { __local float t[4]; }"""),
        "kernel scope"),
    "a __local array sized at run time": (_kernel(
        "__local float t[n];", "__global float* x, int n"),
        "integer literal"),
    "a __local array as a whole": (_kernel("""
        __local float t[4];
        t = 1.0f;"""), "as a whole"),
    "dimension 1": (_kernel("""
        __local float t[4];
        t[get_local_id(1)] = 1.0f;"""), "only dimension 0"),
    "a barrier with no flags": (_kernel("barrier();"), "one argument"),
    "unknown fence flags": (_kernel("barrier(SOME_FENCE);"),
                            "CLK_LOCAL_MEM_FENCE"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_refused(case):
    src, message = REFUSED[case]
    with pytest.raises(KernelLanguageError, match=message):
        fn, _ = KernelProgram(src).launcher("k", 64, 64, 64)
        fn(0, (jnp.zeros(64, jnp.float32),) + (
            (jnp.zeros(4, jnp.float32),) if "__local float* t" in src else ())
           + ((4,) if "int n" in src else ()))


def test_a_barrier_in_a_helper_function_is_refused():
    src = """
    float meet(float v) { barrier(CLK_LOCAL_MEM_FENCE); return v; }
    __kernel void k(__global float* x) { x[get_global_id(0)] = meet(1.0f); }"""
    with pytest.raises(KernelLanguageError, match="helper function"):
        fn, _ = KernelProgram(src).launcher("k", 64, 64, 64)
        fn(0, (jnp.zeros(64, jnp.float32),))


ACCEPTED = {
    "at kernel scope, no tile": "barrier(CLK_GLOBAL_MEM_FENCE);",
    "under a value parameter": "if (n > 2) { barrier(CLK_LOCAL_MEM_FENCE); }",
    "under the group id": """
        if (get_group_id(0) % 2 == 0) { barrier(CLK_LOCAL_MEM_FENCE); }""",
    "in a loop over the number of groups": """
        for (int j = 0; j < get_num_groups(0); j++) {
            barrier(CLK_LOCAL_MEM_FENCE);
        }""",
    "in a loop whose trip count is the group's": """
        int g = get_group_id(0);
        for (int j = 0; j < g; j++) {
            x[get_global_id(0)] += 1.0f;
            barrier(CLK_GLOBAL_MEM_FENCE);
        }""",
    "a literal for the flags": "barrier(0);",
}


@pytest.mark.parametrize("case", sorted(ACCEPTED))
def test_accepted_barriers(case):
    src = _kernel(ACCEPTED[case] + "\nx[get_global_id(0)] += 1.0f;",
                  "__global float* x, int n")
    fn, info = KernelProgram(src).launcher("k", 128, 32, 128)
    out = np.asarray(fn(0, (jnp.zeros(128, jnp.float32),), (3,))[0])
    want = np.ones(128, np.float32)
    if "trip count" in case:
        want += np.repeat(np.arange(4, dtype=np.float32), 32)
    np.testing.assert_array_equal(out, want)
    assert info.local[2] == 1 and info.local[0] == 0


def test_a_quote_in_a_span_field_does_not_swallow_the_fields_behind_it(tmp_path):
    """A profiler annotation's metadata is ``key=value,key=value``: a quote in
    a value opened a quoted string that took ``lane`` and ``tag`` with it (the
    first ``local-memory`` veto read "the kernel's ..."; the cell's span
    readers found no launch of their lane)."""
    import sys

    bench = os.path.join(os.path.dirname(CONFIGS))
    sys.path[:0] = [p for p in (bench,) if p not in sys.path]
    import host_phases
    import xplane

    jax.profiler.start_trace(str(tmp_path))
    try:
        t = TRACER.t0("launch")
        TRACER.record("launch", t, cid=1, lane=3, tag="quoted",
                      veto="the kernel's \"tile\", #1")
    finally:
        jax.profiler.stop_trace()
    spans = [s for line in host_phases.host_lines(
        xplane._profile(xplane.find_xplane(str(tmp_path)))) for s in line
        if s.name == "ck/launch"]
    (stats,) = [s.stats for s in spans]
    stats.pop("win", None)  # the thread's window, where earlier tests left one
    assert stats == {"veto": "the kernels tile; 1", "cid": 1, "lane": 3,
                     "tag": "quoted"}
