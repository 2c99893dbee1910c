"""The passes every lane of a masked loop is sure to make run with no mask
(ISSUE 51): ``codegen._common_walks`` names the loops from their syntax,
``codegen._common_passes`` counts the passes once a launch, ``_exec_masked``
runs them on a scalar counter ahead of the masked loop.

Every case is built three ways on the CPU rig: the build, the build with the
analysis switched off (whose lowered text IS the parent's: the last test of
this module holds ``reduce``'s to the parent's pinned hash) and the scalar
oracle, bit for bit (small integers as float32: every sum is exact).  A loop
that must not get the prologue builds the text it built.  Nothing here yields
a device number.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from cekirdekler_tpu.kernel import codegen, lang  # noqa: E402
from cekirdekler_tpu.kernel.registry import KernelProgram, lowering_meta  # noqa: E402
from tests.kernel_oracle import Oracle  # noqa: E402
from tests.test_local_memory import REDUCE, _eqns, ref  # noqa: E402
from tests.test_pinned_builds import build_sha  # noqa: E402

# ``reduce`` on the parent commit (dcd2b7f), by tests/test_pinned_builds.py
REDUCE_PARENT_SHA = "1bf1bd2d729e425eb9cb9af71632c2bf88e74760a7eed36b0cad5e0d39a42424"

AXPY = """
__kernel void k(__global const float* x, __global float* y, float a, int n) {
    for (int i = get_global_id(0); i < n; i += get_global_size(0)) {
        y[i] = a * x[i] + y[i];
    }
}"""


def _walk(loop: str, walker: str = "int", bound: str = "int",
          before: str = "") -> str:
    """A kernel with no tile around ``loop``, which moves ``i`` from the
    work item's id plus ``c`` and leaves ``float acc``."""
    return f"""
    __kernel void k(__global const float* x, __global float* y, {bound} n,
                    {walker} c) {{
        int gid = get_global_id(0);
        {walker} i = gid + c;
        float acc = 0.0f;
        {before}
        {loop}
        y[gid] = acc + 0.25f * i;
    }}"""


COUNT = "acc += x[i - c] + 1.0f;"
GRID = 3 * 64  # the lanes of a launch of the cases below

# name -> (source, groups, local, elements of x, the values)
PEELED = {
    # SHOC's reduce: a tile, both reads one window a group; groups of 64 have
    # no rows of 128 (the windows are checked pass by pass), groups of 256
    # settle them once, and the common passes keep that loop's conditions
    **{f"reduce, groups of {local}, n {what}": (REDUCE, 4, local, 5 * 8 * local, {"n": np.uint32(n)})
       for local in (64, 256)
       for what, n in (("whole grids", 3 * 8 * local),
                       ("one element short", 3 * 8 * local - 1),
                       ("one element over", 3 * 8 * local + 1),
                       ("below the grid", 8 * local - 70),
                       ("0", 0), ("past the array", 5 * 8 * local + 900))},
    # a grid-stride loop with no tile: gathers and a scatter a pass
    **{f"axpy, n {what}": (AXPY, 3, 64, 5 * GRID, {"a": np.float32(3.0), "n": n})
       for what, n in (("whole grids", 4 * GRID), ("one element short", 4 * GRID - 1),
                       ("one element over", 4 * GRID + 1), ("below the grid", GRID - 5),
                       ("0", 0))},
    # bounds where a sum in the walker's type would wrap: the last lanes land
    # in [n, 2^31) and [n, 2^32), so no walker wraps inside the loop
    "a signed walker near 2^31": (
        _walk(f"while (i < n) {{ {COUNT} i += 1024; }}"), 3, 64, 8192,
        {"n": 2**31 - 1500, "c": 2**31 - 7000}),
    "an unsigned walker near 2^32": (
        _walk(f"while (i < n) {{ {COUNT} i += 1024; }}", "unsigned int",
              "unsigned int"), 3, 64, 8192,
        {"n": np.uint32(2**32 - 1500), "c": np.uint32(2**32 - 7000)}),
    "an unsigned walker over 2^31": (
        _walk(f"while (i <= n) {{ {COUNT} i += 512; }}", "unsigned int",
              "unsigned int"), 3, 64, 8192,
        {"n": np.uint32(2**31 + 2000), "c": np.uint32(2**31 - 3000)}),
    "a signed walker from below 0": (
        _walk(f"while (i < n) {{ {COUNT} i += 100; }}"), 3, 64, 4096,
        {"n": 777, "c": -900}),
    # an ``int`` against an ``unsigned int`` compares without a sign: the
    # lanes below 0 never enter, and the loop has no common pass
    "a signed walker below 0 against an unsigned bound": (
        _walk(f"while (i < n) {{ {COUNT} i += 100; }}", "int", "unsigned int"),
        3, 64, 4096, {"n": np.uint32(777), "c": -100}),
    "a signed walker against an unsigned bound": (
        _walk(f"while (i < n) {{ {COUNT} i += 100; }}", "int", "unsigned int"),
        3, 64, 4096, {"n": np.uint32(1777), "c": 100}),
    "a long walker against an int bound": (
        _walk(f"while (i < n) {{ {COUNT} i += 100; }}", "long", "int"),
        3, 64, 4096, {"n": 1777, "c": 10}),
    # the forms of the condition and of the walk
    "<=": (_walk(f"while (i <= n) {{ {COUNT} i += 64; }}"), 3, 64, 4096,
           {"n": 1000, "c": 8}),
    "<= that the last lane meets": (
        _walk(f"while (i <= n) {{ {COUNT} i += 64; }}"), 3, 64, 4096,
        {"n": 8 + 191 + 5 * 64, "c": 8}),
    "the bound on the left": (
        _walk(f"while (n > i) {{ {COUNT} i += 64; }}"), 3, 64, 4096,
        {"n": 1000, "c": 8}),
    "the bound on the left, >=": (
        _walk(f"while (n >= i) {{ {COUNT} i += 64; }}"), 3, 64, 4096,
        {"n": 1000, "c": 8}),
    "a bound of several terms": (
        _walk(f"while (i < 2 * n + get_global_size(0) - 7) {{ {COUNT} i += 64; }}"),
        3, 64, 4096, {"n": 500, "c": 8}),
    "a for with its step": (
        _walk(f"for (i = gid + c; i < n; i += 48) {{ {COUNT} }}"), 3, 64, 4096,
        {"n": 1000, "c": 8}),
    "a for that declares its walker": ("""
    __kernel void k(__global const float* x, __global float* y, int n, int c) {
        float acc = 0.0f;
        for (int j = get_global_id(0) + c; j < n; j += 2 * get_local_size(0)) {
            acc += x[j] + 1.0f;
        }
        y[get_global_id(0)] = acc;
    }""", 3, 64, 4096, {"n": 1000, "c": 8}),
    "two walkers of which the condition names one": (
        _walk(f"int j = gid; while (i < n) {{ {COUNT} acc += x[j]; "
              "i += 64; j += 7; }"), 3, 64, 4096, {"n": 1000, "c": 8}),
    "a walker moved twice a pass, once back": (
        _walk(f"while (i < n) {{ i += 100; {COUNT} i -= 36; }}"), 3, 64, 4096,
        {"n": 1000, "c": 8}),
    "a walker moved by ++": (
        _walk("while (i < n) { acc += x[i - c + 1] + 1.0f; i++; }"), 3, 64,
        4096, {"n": 230, "c": 8}),
    "a step that is a local assigned once": (
        _walk(f"const int stride = get_global_size(0) + 64; "
              f"while (i < n) {{ {COUNT} i += stride; }}"), 3, 64, 4096,
        {"n": 1500, "c": 8}),
    "a loop inside a loop all lanes leave together": (
        _walk(f"for (int r = 0; r < 3; r++) {{ i = gid + c + r; "
              f"while (i < n) {{ {COUNT} i += 64; }} }}"), 3, 64, 4096,
        {"n": 700, "c": 8}),
    "a loop no lane enters": (
        _walk(f"while (i < n) {{ {COUNT} i += 64; }}"), 3, 64, 4096,
        {"n": -5, "c": 8}),
}

# held to the build without the prologue alone.  C compares an ``int`` below 0
# with an ``unsigned int`` as a large number, and so do both builds; the oracle
# compares the two numbers.  The reduction's reference reads nothing beyond the
# array, where both builds read its last element
NO_ORACLE = {"a signed walker below 0 against an unsigned bound",
             "reduce, groups of 64, n past the array",
             "reduce, groups of 256, n past the array"}

# the loops that must NOT get the prologue: name -> (source, values)
KEPT = {
    "a bound that differs by lane": (
        _walk(f"while (i < n + gid % 3) {{ {COUNT} i += 64; }}"), {}),
    "a bound read at the lane's element": (
        _walk(f"while (i < x[gid]) {{ {COUNT} i += 64; }}"), {}),
    "a bound the body assigns": (
        _walk(f"int m = n; while (i < m) {{ {COUNT} i += 64; m -= 1; }}"), {}),
    "a bound read from a buffer the loop stores to": (
        _walk("while (i < y[0]) { acc += 1.0f; y[gid] = 500.0f; i += 64; }"), {}),
    "a walker moved under an if": (
        _walk(f"while (i < n) {{ {COUNT} if (gid % 2 == 0) {{ i += 64; }} "
              "else { i += 128; } }"), {}),
    "a walker assigned in the body": (
        _walk(f"while (i < n) {{ {COUNT} i = i + 64; }}"), {}),
    "a run-time step": (
        _walk(f"while (i < n) {{ {COUNT} i += c; }}"), {}),
    "a float step": (
        _walk(f"while (i < n) {{ {COUNT} i += 64.0f; }}"), {}),
    "a step of 0": (
        _walk(f"int k = 0; while (i < n && k < 3) {{ {COUNT} k++; }}"), {}),
    "steps that sum to 0": (
        _walk(f"int k = 0; while (i < n) {{ {COUNT} i += 64; i -= 64; "
              "k++; if (k > 2 + gid % 2) { i = n; } }"), {}),
    "a walk downward": (
        _walk(f"while (i > n) {{ {COUNT} i -= 64; }}"), {"n": -300}),
    "a break": (
        _walk(f"while (i < n) {{ if (acc > 4.0f + gid % 2) {{ break; }} "
              f"{COUNT} i += 64; }}"), {}),
    "a continue": (
        _walk(f"while (i < n) {{ i += 64; if (i % 5 == 0) {{ continue; }} "
              "acc += 1.0f; }"), {}),
    "a condition of two terms": (
        _walk(f"while (i < n && acc < 6.0f) {{ {COUNT} i += 64; }}"), {}),
    "a condition that is no order": (
        _walk(f"while (i != n + gid) {{ {COUNT} i += 1; }}"), {"n": 30}),
    "entered under a per-lane if": (
        _walk(f"if (gid % 3 != 0) {{ while (i < n) {{ {COUNT} i += 64; }} }}"), {}),
    "entered under an if every lane takes": (
        _walk(f"if (n > 5) {{ while (i < n) {{ {COUNT} i += 64; }} }}"), {}),
    "entered behind a return": (
        _walk(f"while (i < n) {{ {COUNT} i += 64; }}",
              before="if (gid % 7 == 3) { return; }"), {}),
    "inside a loop the lanes leave apart": (
        _walk(f"int r = 0; while (r < 1 + gid % 2) {{ i = gid + c; "
              f"while (i < n) {{ {COUNT} i += 64; }} r++; }}"), {}),
    "a short walker": (
        _walk(f"while (i < n) {{ {COUNT} i += 64; }}", "short"), {}),
    "a float bound": (
        _walk(f"while (i < n) {{ {COUNT} i += 64; }}", "int", "float"), {}),
    "a run window's loop": (
        _walk("for (i = gid; i < n + gid % 2; i++) { acc += x[i]; }"),
        {"n": 40}),
}


def _build(src: str, size: int, local: int, arrays: tuple, vals: tuple):
    """``(outputs, info, lowered text)`` of the launcher of kernel ``src``."""
    name = lang.parse_kernels(src)[0].name
    fn, info = KernelProgram(src).launcher(name, size, local, size)
    out = [np.asarray(a) for a in fn(0, arrays, vals)]
    return out, info, fn.trace(0, arrays, vals).lower().as_text()


def _three_ways(src: str, groups: int, local: int, elems: int, values: dict,
                seed: int, monkeypatch, oracle: bool = True):
    """``(info, text, the same of the build with the analysis switched off)``,
    with that build's outputs and the oracle's held equal to the build's."""
    kdef = lang.parse_kernels(src)[0]
    size = groups * local
    rng = np.random.default_rng(seed)
    names = [p.name for p in kdef.params if p.is_pointer]
    host = {names[0]: rng.integers(0, 4, elems).astype(np.float32)}
    for other in names[1:]:  # an output: the partials, ``y``
        host[other] = rng.integers(0, 4, max(elems, size)).astype(np.float32)
    vals = tuple(values[p.name] for p in kdef.params if not p.is_pointer)
    arrays = tuple(jnp.asarray(host[k]) for k in names)
    out, info, text = _build(src, size, local, arrays, vals)
    with monkeypatch.context() as mp:
        mp.setattr(codegen, "_common_walks", lambda *a: {})
        want, off_info, off_text = _build(src, size, local, arrays, vals)
    assert off_info.loops_peeled == 0
    assert "peeled" not in lowering_meta([off_info])["loops"]
    for got, off in zip(out, want):
        assert got.tobytes() == off.tobytes()
    if not oracle:
        return info, text, off_info, off_text
    if src == REDUCE:
        # (the oracle models no barrier inside a loop: the configuration's
        # plain reference, which imports nothing of the program)
        host[names[1]][:groups] = ref.partials(host[names[0]], int(values["n"]),
                                               groups, local)
    else:
        Oracle(kdef, local_size=local).run(host, values, size)
    for got, k in zip(out, names):
        np.testing.assert_array_equal(got, host[k])
    return info, text, off_info, off_text


@pytest.mark.parametrize("case", sorted(PEELED))
def test_the_common_passes_of_a_masked_loop_run_with_no_mask(case, monkeypatch):
    src, groups, local, elems, values = PEELED[case]
    info, text, off_info, off_text = _three_ways(
        src, groups, local, elems, values, len(case), monkeypatch,
        oracle=case not in NO_ORACLE)
    assert info.loops_peeled == 1 and text != off_text
    assert (info.loops_counted, info.loops_masked) == (
        off_info.loops_counted, off_info.loops_masked)
    assert lowering_meta([info])["loops"] == (
        f"counted:{info.loops_counted};masked:{info.loops_masked};peeled:1")
    # the masked trace is the last to note how a site was lowered
    assert (info.access, info.scattered) == (off_info.access, off_info.scattered)


# name of a case above -> the passes ALL its lanes make, by hand: the lane that
# starts last is ``c + 191`` (reduce: 3 * 2 L + L - 1) and makes, with ``<``,
# ``(n - start - 1) // step + 1`` passes where ``start < n``
COMMON = {
    "axpy, n whole grids": [4], "axpy, n one element short": [3],
    "axpy, n one element over": [4], "axpy, n below the grid": [0],
    "axpy, n 0": [0],
    "reduce, groups of 64, n whole grids": [3],
    "reduce, groups of 64, n one element short": [3],
    "reduce, groups of 64, n below the grid": [0],
    "reduce, groups of 256, n one element over": [3],
    "reduce, groups of 256, n past the array": [5],
    "a signed walker near 2^31": [6], "an unsigned walker near 2^32": [6],
    "an unsigned walker over 2^31": [10], "a signed walker from below 0": [15],
    "a signed walker below 0 against an unsigned bound": [0],
    "<=": [13], "<= that the last lane meets": [6],
    "a walker moved twice a pass, once back": [13],
    "a loop inside a loop all lanes leave together": [8, 8, 8],
    "a loop no lane enters": [0],
}


@pytest.mark.parametrize("case", sorted(COMMON))
def test_the_common_passes_are_those_of_the_lane_that_starts_last(
        case, monkeypatch):
    """The count itself, as each launch computed it (a callback on the value
    ``_common_passes`` returns): a count too high would run a lane past its
    bound, and one that is 0 where the lanes share passes peels nothing."""
    src, groups, local, elems, values = PEELED[case]
    seen, real = [], codegen._common_passes

    def spied(ctx, peel):
        common = real(ctx, peel)
        jax.debug.callback(lambda c: seen.append(int(c)), common)
        return common

    monkeypatch.setattr(codegen, "_common_passes", spied)
    kdef = lang.parse_kernels(src)[0]
    vals = tuple(values[p.name] for p in kdef.params if not p.is_pointer)
    fn, _info = KernelProgram(src).launcher(kdef.name, groups * local, local,
                                            groups * local)
    jax.block_until_ready(fn(0, (jnp.zeros(elems, jnp.float32), jnp.zeros(
        max(elems, groups * local), jnp.float32)), vals))
    jax.effects_barrier()
    assert seen == COMMON[case]


@pytest.mark.parametrize("case", sorted(KEPT))
def test_a_loop_of_another_form_builds_the_text_it_built(case, monkeypatch):
    src, values = KEPT[case]
    values = {"n": 1000, "c": 8, **values}
    info, text, _off_info, off_text = _three_ways(
        src, 3, 64, 4096, values, len(case), monkeypatch)
    assert info.loops_peeled == 0 and text == off_text
    assert "peeled" not in lowering_meta([info])["loops"]


def test_a_return_inside_a_loop_is_refused_as_it_was():
    from cekirdekler_tpu.errors import KernelLanguageError

    src = _walk(f"while (i < n) {{ if (acc > 3.0f) {{ return; }} {COUNT} i += 64; }}")
    fn, _info = KernelProgram(src).launcher("k", 192, 64, 192)
    with pytest.raises(KernelLanguageError, match="'return' inside a loop"):
        fn(0, (jnp.zeros(512, jnp.float32), jnp.zeros(192, jnp.float32)), (100, 0))


def test_the_peeled_passes_of_reduce_in_its_cell_hold_no_mask():
    """SHOC's launcher as the cell builds it (64 groups of 256 over 2^28
    floats, by shape alone): the walk is THREE ``while``s, and the first, the
    common passes, holds the two one-slice reads, no ``pred[16384]``, no
    reduction over the lanes and no ``cond``, in its body or its condition;
    what it carries is the tile, the counter and the slices' blocks."""
    fn, info = KernelProgram(REDUCE).launcher("reduce", 16384, 256, 16384,
                                              platform="tpu")
    jaxpr = fn.trace(0, (jax.ShapeDtypeStruct((1 << 28,), jnp.float32),
                         jax.ShapeDtypeStruct((64,), jnp.float32)),
                     (np.uint32(1 << 28),)).jaxpr.jaxpr
    walks = [e for e in jaxpr.eqns if e.primitive.name == "while"
             and any(v.aval.shape == (64, 2, 128) for s in _eqns(
                 e.params["body_jaxpr"].jaxpr, "dynamic_slice") for v in s.outvars)]
    assert len(walks) == 3 and info.loops_peeled == 1
    peeled = walks[0]
    for part in (peeled.params["body_jaxpr"].jaxpr, peeled.params["cond_jaxpr"].jaxpr):
        inside = list(part.eqns)
        for eqn in part.eqns:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                inside += sub.eqns
        assert not [e for e in inside if e.primitive.name in ("cond", "while", "gather")]
        assert not [e for e in inside if e.primitive.name.startswith(("reduce", "arg"))]
        # (the tile's store selects under a broadcast ``True``, which the
        # compiler folds; no mask is COMPUTED, and none is as long as the lanes)
        assert not [e for e in inside if e.outvars[0].aval.shape != () and
                    e.primitive.name in ("lt", "le", "gt", "ge", "eq", "ne",
                                         "and", "or", "not")]
        assert not [v for e in inside for v in (*e.invars, *e.outvars)
                    if getattr(v.aval, "shape", None) == (16384,)
                    and v.aval.dtype == jnp.bool_]
    body = peeled.params["body_jaxpr"].jaxpr
    assert len([s for s in _eqns(body, "dynamic_slice")
                if s.outvars[0].aval.shape == (64, 2, 128)]) == 2
    carried = sorted((v.aval.shape, str(v.aval.dtype)) for v in body.outvars)
    assert carried == [((), "int32"), ((), "int32"), ((), "uint32"),
                       ((64, 256), "float32")]
    # the masked loops behind it are the parent's: a mask in each
    for later in walks[1:]:
        assert [v for v in later.params["body_jaxpr"].jaxpr.outvars
                if v.aval.shape == (16384,) and v.aval.dtype == jnp.bool_]


def test_with_the_analysis_switched_off_reduce_is_the_parents_program(monkeypatch):
    assert build_sha("shoc_reduction.cl", "reduce") != REDUCE_PARENT_SHA
    monkeypatch.setattr(codegen, "_common_walks", lambda *a: {})
    assert build_sha("shoc_reduction.cl", "reduce") == REDUCE_PARENT_SHA
