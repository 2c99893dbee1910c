"""Vector types of the kernel language through ``KernelProgram`` and
``compute()`` on the CPU rig (ISSUE 50; docs/KERNEL_LANGUAGE.md, *Vector
types*).  Nothing here yields a device number.

- SHOC's ``compute_lj_force`` from the benchmark's own file
  (``benchmark/configs/shoc_md.cl``) against the configuration's plain
  reference (``shoc_md_ref``, which imports nothing of the program) on seeded
  atoms; against the SAME kernel written component by component
  (``benchmark/checks/fixtures/shoc_md_components.cl``), byte for byte; against
  the scalar oracle (``tests/kernel_oracle.py``);
- every form of the index for a load and a store of ``float4``, ``float2``
  and ``int4``, each three ways: the build with plain gathers, the build with
  the chip's row gathers, the oracle (data are small integers, so every
  comparison is exact);
- ``elements_per_work_item`` 4 on one lane and split over two and four lanes
  with the balancer moving the ranges;
- what ckprove proves of the kernel's flags, the named validation, the span
  fields, the Pallas veto, and every refusal by its name.
"""

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
from cekirdekler_tpu.errors import KernelCompileError, KernelLanguageError  # noqa: E402
from cekirdekler_tpu.hardware import platforms  # noqa: E402
from cekirdekler_tpu.kernel import codegen, lang, pallas_backend  # noqa: E402
from cekirdekler_tpu.kernel.registry import KernelProgram, lowering_meta  # noqa: E402

from kernel_oracle import Oracle  # noqa: E402

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def source(*parts: str) -> str:
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as f:
        return f.read()


MD = source("configs", "shoc_md.cl")
MD_COMPONENTS = source("checks", "fixtures", "shoc_md_components.cl")
_spec = importlib.util.spec_from_file_location(
    "shoc_md_ref", os.path.join(BENCH, "configs", "shoc_md_ref.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

MD_FLAGS = ({"read": False, "write": True, "elements_per_work_item": 4},
            {"read": True, "write": False, "elements_per_work_item": 4},
            {"read_only": True})


@pytest.fixture(scope="module")
def devs():
    return platforms().cpus()


def atoms(side: int, neighbours: int, seed: int):
    """Seeded atoms of the configuration's own recipe on a ``side``^3 lattice."""
    cfg = {"atoms": side ** 3, "lattice": [side] * 3, "neighbours": neighbours,
           "spacing": 0.4775, "jitter": 0.15, "displacement": 0.01,
           "cutsq": 16.0}
    data, values = ref.inputs(cfg, {"n": side ** 3}, np.random.default_rng(seed))
    return cfg, data, values


def run(devs, lanes: int, src: str, kernel: str, arrays: list, n: int,
        local: int, values=(), flags=(), computes: int = 1, cid: int = 48):
    """``computes`` synchronous ``compute()`` calls; the host arrays after the
    last, and the lanes' ranges after each."""
    bound = [ClArray(a.copy(), name=f"p{k}", **(flags[k] if flags else {}))
             for k, a in enumerate(arrays)]
    cr = NumberCruncher(devs.subset(lanes), src)
    try:
        first, *rest = bound
        ranges = []
        for k in range(computes):
            first.next_param(*rest).compute(cr, cid, kernel, n, local,
                                            values=tuple(values))
            ranges.append(tuple(cr.ranges_of(cid)))
            if k == 0 and lanes > 1 and computes > 1:
                # a lopsided split for the balancer to move back from
                cr.cores.global_ranges[cid] = (
                    [local] * (lanes - 1) + [n - local * (lanes - 1)])
        assert cr.number_of_errors_happened == 0
        return [np.array(b.host()) for b in bound], ranges
    finally:
        cr.dispose()


def md_arrays(data) -> list:
    return [data["force3"], data["position"], data["neighList"]]


# -- SHOC's compute_lj_force -------------------------------------------------

@pytest.mark.parametrize("neighbours", [16, 128])
def test_md_forces_are_the_references(devs, neighbours):
    cfg, data, values = atoms(16, neighbours, seed=48 + neighbours)
    n = cfg["atoms"]
    (got, _pos, _list), _ = run(devs, 1, MD, "compute_lj_force",
                                md_arrays(data), n, 256, values, MD_FLAGS)
    want = ref.forces(data["position"].reshape(n, 4),
                      data["neighList"].reshape(neighbours, n), np.arange(n),
                      *values[1:4])
    got = got.reshape(n, 4)
    assert np.abs(got[:, :3] - want).max() / np.abs(want).max() < 1e-5
    assert not got[:, 3].any()  # the kernel stores f.w = 0 over the poison
    inside = ref.forces(data["position"].reshape(n, 4),
                        data["neighList"].reshape(neighbours, n), np.arange(n),
                        *values[1:4], without_cutoff=True)
    assert np.abs(inside - want).max() > 0  # both sides of the branch ran


@pytest.mark.parametrize("neighbours", [16, 128])
def test_the_vector_kernel_and_its_components_give_the_same_bytes(devs, neighbours):
    cfg, data, values = atoms(16, neighbours, seed=7)
    outs = [run(devs, 1, src, "compute_lj_force", md_arrays(data),
                cfg["atoms"], 256, values, MD_FLAGS)[0][0]
            for src in (MD, MD_COMPONENTS)]
    np.testing.assert_array_equal(outs[0].view(np.uint32), outs[1].view(np.uint32))


@pytest.mark.parametrize("platform", [None, "tpu"])
def test_md_against_the_oracle_and_what_was_built(platform):
    cfg, data, values = atoms(8, 12, seed=3)
    n = cfg["atoms"]
    kdef, = lang.parse_kernels(MD)
    fn, info = codegen.build_kernel_fn(kdef, n, 256, n, platform)
    out = jax.jit(lambda *a: fn(*a, (n,)))(
        0, tuple(jnp.asarray(a) for a in md_arrays(data)), values)
    want = {k: a.copy() for k, a in zip(("force3", "position", "neighList"),
                                        md_arrays(data))}
    names = [p.name for p in kdef.params if not p.is_pointer]
    Oracle(kdef, 256).run(want, dict(zip(names, values)), n)
    # (XLA contracts a * b + c where the oracle rounds twice, so the last bits
    # of a sum may differ: the component kernel is the byte-for-byte witness)
    got = np.asarray(out[0])
    assert np.abs(got - want["force3"]).max() / np.abs(want["force3"]).max() < 1e-6
    assert info.access == {"slice": 3, "strided": 0, "uniform": 0, "gather": 1,
                           "scatter": 0, "carried": 0}
    assert info.vector == (2, (4,), 1, 1, 1) and info.scattered == ()
    assert (info.loops_counted, info.loops_masked) == (1, 0)
    meta = lowering_meta([info])
    assert meta["vector"] == "params:2;width:4;loads:1;gathers:1;stores:1"
    assert meta["access"] == ("slice:3;strided:0;uniform:0;gather:1;scatter:0;"
                              "carried:0") and "scatter" not in meta
    assert info.views == ((codegen.ViewSpec(1, "rows"),) if platform else ())


def test_the_component_kernel_pays_four_accesses_for_one():
    """What a port had to write before (ROADMAP M13's sentence): three strided
    walks for one load, three gathers a neighbour, four scatters for one
    store."""
    kdef, = lang.parse_kernels(MD_COMPONENTS)
    fn, info = codegen.build_kernel_fn(kdef, 1024, 256, 1024, "tpu")
    n, k = 1024, 4
    jax.eval_shape(lambda *a: fn(*a, (n,)), 0, (
        jax.ShapeDtypeStruct((4 * n,), jnp.float32),) * 2 + (
        jax.ShapeDtypeStruct((k * n,), jnp.int32),),
        (k, np.float32(16), np.float32(1.5), np.float32(2), n))
    assert info.access == {"slice": 1, "strided": 3, "uniform": 0, "gather": 3,
                           "scatter": 4, "carried": 0}
    assert info.scattered == (4, 4, 4, 4) and info.vector == ()
    assert "vector" not in lowering_meta([info])


# -- elements_per_work_item 4 over lanes -------------------------------------

@pytest.mark.parametrize("lanes", [1, 2, 4])
def test_lanes_give_the_same_bytes_while_the_balancer_moves_the_ranges(devs, lanes):
    cfg, data, values = atoms(16, 16, seed=11)
    n = cfg["atoms"]
    (one, _p, _l), _ = run(devs, 1, MD, "compute_lj_force", md_arrays(data), n,
                           256, values, MD_FLAGS, cid=480)
    (got, pos, neigh), ranges = run(devs, lanes, MD, "compute_lj_force",
                                    md_arrays(data), n, 256, values, MD_FLAGS,
                                    computes=6, cid=481 + lanes)
    np.testing.assert_array_equal(got.view(np.uint32), one.view(np.uint32))
    np.testing.assert_array_equal(pos, data["position"])   # never written back
    np.testing.assert_array_equal(neigh, data["neighList"])
    assert all(len(r) == lanes and sum(r) == n and all(x % 256 == 0 for x in r)
               for r in ranges)
    if lanes > 1:
        assert len(set(ranges)) > 1, ranges  # the balancer moved them


def test_a_ranged_share_that_would_cut_a_vector_is_refused_by_name(devs):
    cfg, data, values = atoms(8, 4, seed=2)
    for epw in (1, 2, 3):
        flags = ({**MD_FLAGS[0], "elements_per_work_item": epw},) + MD_FLAGS[1:]
        with pytest.raises(ComputeValidationError,
                           match="vector-elements-per-work-item.*force3|"
                                 "vector-elements-per-work-item.*p0"):
            run(devs, 1, MD, "compute_lj_force", md_arrays(data), cfg["atoms"],
                256, values, flags)
    # a whole transfer is cut by no range: any share passes
    whole = ({"read": False, "write": True, "write_all": True},
             {"read": True, "write": False}, {"read_only": True})
    (got, _p, _l), _ = run(devs, 1, MD, "compute_lj_force", md_arrays(data),
                           cfg["atoms"], 256, values, whole)
    assert not (got == -1).any()
    with pytest.raises(ComputeValidationError, match="vector-array-length"):
        arrays = md_arrays(data)
        arrays[1] = np.concatenate([arrays[1], np.zeros(2, np.float32)])
        run(devs, 1, MD, "compute_lj_force", arrays, cfg["atoms"], 256, values,
            MD_FLAGS)


def test_ckprove_reads_a_vector_parameter_in_elements():
    kdef, = lang.parse_kernels(MD)
    s = summarize_kernel(kdef)
    (write,) = s.writes["force3"]
    assert (write.av.coef, write.av.lo, write.av.hi) == (4.0, 0.0, 3.0)
    own, gathered = sorted(s.reads["position"], key=lambda a: a.av.coef is None)
    assert (own.av.coef, own.av.lo, own.av.hi) == (4.0, 0.0, 3.0)
    assert gathered.av.coef is None and "neighList" not in s.writes
    prog = KernelProgram(MD)

    def rows(*flags):
        return tuple(flag_row(ClArray(np.zeros(16, np.float32), **f)) for f in flags)

    for lanes in (1, 4, None):
        assert not prog.verify(("compute_lj_force",), rows(*MD_FLAGS),
                               lanes=lanes).errors
    # the force's share at one element a work item leaves its partition
    cut = prog.verify(("compute_lj_force",), rows(
        {"read": False, "write": True}, *MD_FLAGS[1:]))
    assert {f.kind for f in cut.errors} == {"off-partition-write"}
    # positions are gathered: a ranged upload would hand a lane its own alone
    part = prog.verify(("compute_lj_force",), rows(
        MD_FLAGS[0], {**MD_FLAGS[1], "partial_read": True}, MD_FLAGS[2]))
    assert {f.kind for f in part.errors} == {"partial-read-gather"}
    assert not prog.roaming_stores(("compute_lj_force",), (4, 4, 1))
    assert prog.roaming_stores(("compute_lj_force",), (1, 4, 1))
    assert prog.vector_widths("compute_lj_force") == (4, 4, 0)
    assert KernelProgram(MD_COMPONENTS).vector_widths("compute_lj_force") == ()


def test_a_tpu_lane_takes_the_xla_half_with_a_named_veto():
    prog = KernelProgram(MD)
    _fn, info = prog.launcher("compute_lj_force", 256, 256, 256, platform="tpu")
    assert info.lowering == "xla" and info.veto.startswith("vector-types")
    kdef, = lang.parse_kernels(MD)
    with pytest.raises(pallas_backend.PallasUnsupported, match="vector-types"):
        pallas_backend.build_kernel_fn_pallas(kdef, 256, 256, 256, interpret=True)
    assert lang.uses_vectors(kdef)
    assert not lang.uses_vectors(lang.parse_kernels(MD_COMPONENTS)[0])


# -- every form of the index, three ways -------------------------------------

TYPES = {"float4": ("float", 4, np.float32), "float2": ("float", 2, np.float32),
         "int4": ("int", 4, np.int32)}
N, LOCAL = 512, 64

# name -> (body with T / E for the vector and its element type, the kinds the
# accesses of ``a`` and ``out`` must take, in ``access``'s names).  ``a`` holds
# 2 N vectors, ``out`` N (2 N where the store is strided), ``ix`` a
# permutation of [0, N), ``cnt`` small counts, ``k`` a run-time scalar
FORMS = {
    "load slice": ("out[i] = a[i];", {"slice": 2}),
    "load slice at an offset": ("out[i] = a[i + 7];", {"slice": 2}),
    "load slice at a run-time offset": ("out[i] = a[i + k];", {"slice": 2}),
    "load strided": ("out[i] = a[2 * i + 1];", {"strided": 1, "slice": 1}),
    "load uniform": ("out[i] = a[k];", {"uniform": 1, "slice": 1}),
    "load gather": ("out[i] = a[ix[i]];", {"gather": 1, "slice": 2}),
    "load beyond the end clamps": ("out[i] = a[ix[i] * 5 - 600];",
                                   {"gather": 1, "slice": 2}),
    "load under a mask": ("""
        T v = (T)(3);
        if (i % 3 == 0) { v = a[ix[i]]; }
        out[i] = v;""", {"gather": 1, "slice": 2}),
    "load in a masked loop with break": ("""
        T v = (T)(0);
        for (int j = 0; j < cnt[i]; j++) {
            T w = a[ix[(i + j) % 512]];
            v += w;
            if (w.x > 40) { break; }
        }
        out[i] = v;""", {"gather": 2, "slice": 2}),
    "store slice": ("T v = a[i]; v.y = v.y + 1; out[i] = v;", {"slice": 2}),
    "store slice under a mask": (
        "if (i % 3 != 1) { out[i] = a[i] * 2; }", {"slice": 2}),
    "store slice under a uniform if": (
        "if (k > 3) { out[i] = a[i] + a[i + 1]; }", {"slice": 3}),
    "store strided": ("out[2 * i + 1] = a[i];", {"scatter": 1, "slice": 1}),
    "store uniform": ("out[k] = (T)(5);", {"scatter": 1}),
    "store gather": ("out[ix[i]] = a[i];", {"scatter": 1, "slice": 2}),
    "store under a mask": ("if (i % 4 == 2) { out[ix[i]] = a[i]; }",
                           {"scatter": 1, "slice": 2}),
    "store in a masked loop with break": ("""
        for (int j = 0; j < cnt[i]; j++) {
            T w = a[(i + j) % 512];
            out[ix[i]] = w + (T)(j);
            if (w.y > 30) { break; }
        }""", {"scatter": 1, "gather": 1}),
    "a local carried through a counted loop": ("""
        T v = a[i];
        for (int j = 0; j < k; j++) { v = v + a[j]; v.x -= 1; }
        out[i] = v;""", {"uniform": 1, "slice": 2}),
    "literals, a broadcast scalar, unary minus": ("""
        T v = {1, 2};
        T w = (T)(3, 4);
        E s = a[i].y;
        v = -v * s + w / (T)(2) - 1;
        v.s1 = v.s0 + s;
        out[i] = v;""", {"slice": 2}),
    "compound assignments": ("""
        T v = a[i];
        v += a[i + 1]; v -= 3; v *= (T)(2); v /= 2; v.x *= 5;
        out[i] = v;""", {"slice": 3}),
    "a helper takes and returns vectors": ("""
        out[i] = twice(a[i], 3) + twice((T)(1), i);""", {"slice": 2}),
}
HELPER = "T twice(T v, E s) { T w = v + v; w.y += s; return w; }\n"


def form_source(body: str, vtype: str) -> str:
    elem, n, _dt = TYPES[vtype]
    if n == 4:  # the two-component literals, made four
        body = body.replace("{1, 2}", "{1, 2, 3, 4}").replace(
            "(T)(3, 4)", "(T)(3, 4, 5, 6)")
    text = (HELPER + """
    __kernel void form(__global T* a, __global T* out, __global int* ix,
                       __global int* cnt, int k) {
        int i = get_global_id(0);
        %s
    }""" % body)
    return text.replace("T", vtype).replace("E ", elem + " ")


def form_arrays(vtype: str, seed: int) -> dict:
    _elem, n, dt = TYPES[vtype]
    rng = np.random.default_rng(seed)
    return {"a": rng.integers(1, 50, 2 * N * n).astype(dt),
            "out": np.full(2 * N * n, -9, dt),
            "ix": rng.permutation(N).astype(np.int32),
            "cnt": rng.integers(0, 6, N).astype(np.int32)}


@pytest.mark.parametrize("vtype", sorted(TYPES))
@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_form_three_ways(form, vtype):
    body, kinds = FORMS[form]
    kdef, = lang.parse_kernels(form_source(body, vtype))
    arrays = form_arrays(vtype, seed=len(form))
    want = {k: v.copy() for k, v in arrays.items()}
    Oracle(kdef, LOCAL).run(want, {"k": 5}, N)
    for platform in (None, "tpu"):
        fn, info = codegen.build_kernel_fn(kdef, N, LOCAL, N, platform)
        out = jax.jit(fn)(0, tuple(jnp.asarray(v) for v in arrays.values()),
                          (np.int32(5),))
        for name, got in zip(arrays, out):
            np.testing.assert_array_equal(np.asarray(got), want[name], err_msg=name)
        for kind, count in kinds.items():
            assert info.access[kind] == count, (platform, info.access)
        wide = [k for k in ("slice", "strided", "uniform", "gather", "scatter")
                for _ in range(info.access[k])]
        # every access of ``a`` and ``out`` is ONE vector access, whatever N
        assert sum(info.vector[2:]) == len(wide) - body.count("ix[") \
            - body.count("cnt["), (info.vector, info.access)
        assert info.vector[:2] == (2, (TYPES[vtype][1],))
        assert all(w == TYPES[vtype][1] * 4 for w in info.scattered)


def test_a_form_through_compute_split_over_lanes(devs):
    """A masked loop with a break, a vector local carried through it, a ranged
    write-back of ``float2`` (``elements_per_work_item`` 2), on four lanes."""
    body, _kinds = FORMS["load in a masked loop with break"]
    src = form_source(body, "float2")
    arrays = form_arrays("float2", seed=9)
    flags = ({"read_only": True}, {"read": False, "write": True,
                                   "elements_per_work_item": 2},
             {"read_only": True}, {"read_only": True})
    want = {k: v.copy() for k, v in arrays.items()}
    Oracle(lang.parse_kernels(src)[0], LOCAL).run(want, {"k": 5}, N)
    for lanes in (1, 4):
        got, _ = run(devs, lanes, src, "form", list(arrays.values()), N, LOCAL,
                     (5,), flags, computes=3, cid=4900 + lanes)
        np.testing.assert_array_equal(got[1][:2 * N], want["out"][:2 * N])


def test_a_buffer_that_is_no_whole_number_of_vectors_is_refused():
    kdef, = lang.parse_kernels(form_source("out[i] = a[i];", "float4"))
    fn, _info = codegen.build_kernel_fn(kdef, N, LOCAL, N)
    with pytest.raises(KernelCompileError, match="no whole number of float4"):
        jax.eval_shape(fn, 0, (jax.ShapeDtypeStruct((4 * N + 2,), jnp.float32),
                               jax.ShapeDtypeStruct((4 * N,), jnp.float32),
                               jax.ShapeDtypeStruct((N,), jnp.int32),
                               jax.ShapeDtypeStruct((N,), jnp.int32)), (5,))


# -- refusals, each by its name ----------------------------------------------

def _k(body: str, params: str = "__global float4* a, __global float* s") -> str:
    return "__kernel void k(%s) {\n int i = get_global_id(0);\n%s\n}" % (params, body)


REFUSED = {
    "a width of 3": ("vector-width", _k("float3 v = a[i];")),
    "a width of 8 as a parameter": ("vector-width", _k("", "__global float8* a")),
    "a width of 16": ("vector-width", _k("int16 v;")),
    "double4": ("vector-width", _k("double4 v;")),
    "char4": ("vector-width", _k("", "__global uchar4* a")),
    "half2": ("vector-width", _k("half2 v;")),
    "a vector value parameter of a kernel": (
        "vector-value-parameter", _k("", "__global float4* a, float4 v")),
    "an array of vectors": ("vector-array", _k("float4 v[3];")),
    "a __local array of vectors": (
        "vector-local-memory", _k("__local float4 t[64];")),
    "a swizzle of two components": ("vector-swizzle", _k("float4 v = a[i]; s[i] = v.xy.x;")),
    "a swizzle on the left": ("vector-swizzle", _k("float4 v = a[i]; v.xyz = 1;")),
    ".lo": ("vector-swizzle", _k("float4 v = a[i]; s[i] = v.lo;")),
    ".s01": ("vector-swizzle", _k("float4 v = a[i]; s[i] = v.s01;")),
    "a member a vector has not": ("vector-member", _k("float4 v = a[i]; s[i] = v.q;")),
    ".z of a float2": ("vector-member", _k("float2 v = (float2)(1); s[i] = v.z;")),
    ".w of a float2 element": (
        "vector-member", _k("s[i] = b[i].w;", "__global float2* b, __global float* s")),
    "a member of a scalar": ("vector-member", _k("float v = 1; s[i] = v.x;")),
    "a member of a scalar expression": ("vector-member", _k("s[i] = (s[i] + 1).x;")),
    "one component of an element stored alone": (
        "vector-member-store", _k("a[i].x = 1;")),
    "a comparison of vectors": (
        "vector-comparison", _k("float4 v = a[i]; if (v.x > 0) { v = v < a[i]; }")),
    "a vector as a condition": ("vector-comparison", _k("if (a[i]) { s[i] = 1; }")),
    "a logical operator on a vector": (
        "vector-comparison", _k("if (a[i] && s[i] > 0) { s[i] = 1; }")),
    "?: with vector operands": (
        "vector-select", _k("float4 v = s[i] > 0 ? a[i] : a[i + 1]; a[i] = v;")),
    "% on vectors": ("vector-operator", _k(
        "b[i] = b[i] % 3;", "__global int4* b")),
    "a shift of a vector": ("vector-operator", _k(
        "b[i] = b[i] << 1;", "__global int4* b")),
    "~ on a vector": ("vector-operator", _k("b[i] = ~b[i];", "__global int4* b")),
    "vload4": ("vload-vstore", _k("float4 v = vload4(i, s);")),
    "vstore4": ("vload-vstore", _k("vstore4(a[i], i, s);")),
    "dot": ("vector-builtin", _k("s[i] = dot(a[i], a[i]);")),
    "length": ("vector-builtin", _k("s[i] = length(a[i]);")),
    "cross": ("vector-builtin", _k("a[i] = cross(a[i], a[i + 1]);")),
    "sqrt of a vector": ("vector-builtin", _k("a[i] = sqrt(a[i]);")),
    "fmax of vectors": ("vector-builtin", _k("a[i] = fmax(a[i], a[i + 1]);")),
    "convert_int4": ("vector-conversion", _k("float4 v = a[i]; s[i] = convert_int4(v).x;")),
    "as_uint4": ("vector-conversion", _k("float4 v = a[i]; s[i] = as_uint4(v).x;")),
    "a cast of a vector to a scalar": ("vector-conversion", _k("s[i] = (float)a[i];")),
    "a cast of a value to a vector": ("vector-conversion", _k("float4 v = (float4)s[i];")),
    "a float4 assigned to a float2": (
        "vector-conversion", _k("float2 v = (float2)(1); v = a[i];")),
    "a float4 stored to a float2 buffer": (
        "vector-conversion", _k("b[i] = a[i];", "__global float4* a, __global float2* b")),
    "a vector stored to a scalar buffer": ("vector-conversion", _k("s[i] = a[i];")),
    "a vector assigned to a scalar local": (
        "vector-conversion", _k("float v = 0; v = a[i]; s[i] = v;")),
    "float4 + int4": ("vector-conversion", _k(
        "a[i] = a[i] + b[i];", "__global float4* a, __global int4* b")),
    "a literal with three scalars": ("vector-literal", _k("float4 v = (float4)(1, 2, 3);")),
    "a literal with a vector inside": (
        "vector-literal", _k("float2 h = (float2)(1); float4 v = (float4)(h, 1, 2, 3);")),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_what_is_outside_the_core_is_refused_by_name(case):
    name, src = REFUSED[case]
    with pytest.raises(KernelLanguageError, match=r"\b%s\b" % name):
        kdef, = lang.parse_kernels(src)
        fn, _info = codegen.build_kernel_fn(kdef, 64, 64, 64)
        arrays = tuple(jax.ShapeDtypeStruct(
            (512,), codegen.ctype_to_dtype((lang.vector_of(p.ctype) or (p.ctype,))[0]))
            for p in kdef.params if p.is_pointer)
        jax.eval_shape(fn, 0, arrays, ())
