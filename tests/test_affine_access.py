"""Affine accesses (``s * gid + u``, ``u`` the same in every lane) lower to
slices and strided windows, never to a per-lane gather or a scatter, and the
results are the gather form's to the last bit.

The gather form of a kernel is the same text with every index written
``(...) / 1``: a division the affine tracker does not follow, so the load
falls to the per-lane gather and the store to the scatter that every such
access took before.  Nothing here yields a device number.
"""

import collections

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from cekirdekler_tpu.kernel import codegen, lang  # noqa: E402
from cekirdekler_tpu.kernel.registry import KernelProgram, lowering_meta  # noqa: E402
from tests.kernel_oracle import Oracle  # noqa: E402

MVT = """
__kernel void mvt_kernel1(__global float *a, __global float *x1, __global float *x2,
                          __global float *y1, __global float *y2, int n)
{
    int i = get_global_id(0);
    if (i < n)
    {
        int j;
        for (j = 0; j < n; j++)
        {
            x1[i] += a[i * n + j] * y1[j];
        }
    }
}
__kernel void mvt_kernel2(__global float *a, __global float *x1, __global float *x2,
                          __global float *y1, __global float *y2, int n)
{
    int i = get_global_id(0);
    if (i < n)
    {
        int j;
        for (j = 0; j < n; j++)
        {
            x2[i] += a[j * n + i] * y2[j];
        }
    }
}
"""


def primitives(jaxpr, out=None) -> collections.Counter:
    """Every primitive of a jaxpr and of the jaxprs inside it, counted."""
    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        out[eqn.primitive.name] += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    primitives(inner, out)
    return out


@pytest.mark.parametrize("name, kinds", [
    ("mvt_kernel1", {"strided": 1, "uniform": 1, "slice": 0}),
    ("mvt_kernel2", {"slice": 1, "uniform": 1, "strided": 0})])
def test_the_two_walks_hold_no_gather_and_no_scatter(name, kinds):
    n = 512
    prog = KernelProgram(MVT)
    fn, info = prog.launcher(name, n, 256, n, platform="tpu")
    assert info.lowering == "xla" and "lane-uniform" in info.veto
    a = jax.ShapeDtypeStruct((n * n,), jnp.float32)
    v = jax.ShapeDtypeStruct((n,), jnp.float32)
    off = jax.ShapeDtypeStruct((), jnp.int32)
    # keyed by n, as a call with a plain integer is (the launcher's __call__)
    seen = primitives(fn.trace(off, (a, v, v, v, v), (n,), (n,)).jaxpr.jaxpr)
    assert seen["gather"] == 0 and seen["scatter"] == 0, seen
    assert seen["dynamic_slice"] > 0
    assert info.access == {**kinds, "gather": 0, "scatter": 0, "carried": 1}
    assert info.keyed == {"n": n}
    meta = lowering_meta((info,))
    assert meta["keys"] == f"n={n}" and "gather:0;scatter:0" in meta["access"]
    # a traced pitch stays an argument: no window, the store still a select
    seen = primitives(fn.trace(off, (a, v, v, v, v), (n,)).jaxpr.jaxpr)
    assert seen["scatter"] == 0 and info.keyed == {}
    assert seen["gather"] == (9 if name == "mvt_kernel1" else 0)


def test_access_is_summed_over_the_kernels_of_a_sequence():
    n = 256
    prog = KernelProgram(MVT)
    arrays = (jnp.zeros(n * n, jnp.float32),
              *(jnp.zeros(n, jnp.float32) for _ in range(4)))
    infos = []
    for name in ("mvt_kernel1", "mvt_kernel2"):
        for chunk in (128, 64):  # two rungs of one kernel count once
            fn, info = prog.launcher(name, chunk, 64, n, platform="cpu")
            fn(0, arrays, (n,))
            infos.append(info)
    assert lowering_meta(infos)["access"] == (
        "slice:1;strided:1;uniform:2;gather:0;scatter:0;carried:2")


def test_pitch_params_come_from_the_syntax_tree():
    src = """
    __kernel void k(__global float* a, __global float* o, int n, int m, int w, float s) {
        int i = get_global_id(0);
        int row = i * w;
        float t = (float)(i * m) * s;
        o[i] = a[row + n] + t;
    }
    __kernel void plain(__global float* a, __global float* o, int n) {
        int i = get_global_id(0);
        o[i] = a[i] * (float)(n * 2);
    }"""
    k, plain = lang.parse_kernels(src)
    assert codegen.pitch_params(k) == (2,)  # w, through the local ``row``
    assert codegen.pitch_params(plain) == ()


def test_a_pitch_keys_one_executable_a_value():
    """Plain integers are keys (one trace a value, none for a value seen
    before); a pitch that arrives as an array stays a runtime argument."""
    prog = KernelProgram(MVT)
    traces = []

    def arrays(n):
        return (jnp.ones(n * n, jnp.float32),
                *(jnp.ones(n, jnp.float32) for _ in range(4)))

    fn, info = prog.launcher("mvt_kernel1", 128, 64, 128, platform="cpu")
    for n, keyed in ((128, {"n": 128}), (128, {"n": 128}),
                     (np.int32(128), {"n": 128}), (jnp.int32(128), {})):
        before = fn._cache_size()
        out = fn(0, arrays(128), (n,))
        traces.append(fn._cache_size() - before)
        assert info.keyed == keyed or traces[-1] == 0
        np.testing.assert_allclose(np.asarray(out[1]), 129.0)  # 1 + 128 ones
    # (a numpy integer is the same key and, since a dispatch packs its plain
    # scalars by the parameters' types, the same entry as a Python int)
    assert traces == [1, 0, 0, 1]
    assert prog.compiled_count == 1  # one launcher: the keys live in its jit


def test_a_factor_that_sweeps_stops_keying_the_launcher():
    """A pitch is a shape, and a process has few; an index factor that
    changes from call to call (``a[i * s]``: a reduction's stride) would
    otherwise compile one launcher a value.  After ``KEYED_BUILDS`` values
    the rest ride ONE build that takes the factor as a runtime argument,
    and every result is right."""
    src = """
    __kernel void k(__global float* a, __global float* o, int s) {
        int i = get_global_id(0);
        o[i] = a[i * s] + 1.0f;
    }"""
    prog = KernelProgram(src)
    fn, info = prog.launcher("k", 64, 64, 64, platform="cpu")
    a = jnp.arange(64 * 16, dtype=jnp.float32)
    compiles = []
    for s in list(range(1, 13)) + [2, 3, 9, 12]:
        before = fn._cache_size()
        out = fn(0, (a, jnp.zeros(64, jnp.float32)), (s,))
        compiles.append(fn._cache_size() - before)
        np.testing.assert_array_equal(
            np.asarray(out[1]), np.arange(64, dtype=np.float32) * s + 1)
    keyed = fn.KEYED_BUILDS
    # one build a value up to the bound, one more for all that follow
    assert compiles == [1] * keyed + [1] + [0] * (12 - keyed - 1) + [0] * 4
    assert info.keyed == {}  # the newest build took ``s`` at run time


# -- bit-identity with the gather form ---------------------------------------

FORMS = {
    # the column walk: stride 1, a runtime offset
    "col": "a[j * n + i]",
    # the row walk: the stride a value parameter, a window of columns
    "row": "a[i * n + j]",
    # a field of an array of structures, elements_per_work_item 3
    "aos": "a[3 * i + j]",
    # a field of a structure of arrays
    "soa": "a[i + j * n]",
}
# what bounds the loop: the row walk is proved inside its row by the pitch
# itself, the structure's fields by a literal; the contiguous forms need no
# proof (an unproved window puts the clamped elements in itself)
BOUNDS = {"col": "m", "row": "n", "aos": "3", "soa": "m"}
COUNTED = """
__kernel void k(__global float* a, __global float* x, __global float* y, int n, int m) {
    int i = get_global_id(0);
    if (i < n) {
        for (int j = 0; j < BOUND; j++) {
            x[i] += IDX * y[j];
        }
    }
}"""
# a loop that lanes leave on different passes, inside a counted one
MASKED = """
__kernel void k(__global float* a, __global float* x, __global float* y, int n, int m) {
    int i = get_global_id(0);
    if (i < n) {
        for (int j = 0; j < BOUND; j++) {
            int t = 0;
            while (t < (i & 3)) {
                x[i] += IDX * y[j];
                t++;
            }
        }
    }
}"""
# no guard, and the index runs past both ends: loads clamp, stores drop
ENDS = """
__kernel void k(__global float* a, __global float* x, __global float* y, int n, int m) {
    int i = get_global_id(0);
    for (int j = 0; j < BOUND; j++) {
        x[i + 5] += IDX * y[j] + SHIFTED;
    }
}"""


def gather_form(src: str) -> str:
    """Every index of the kernel wrapped in ``( ... ) / 1``."""
    kdef = lang.parse_kernels(src)[0]
    for ix in codegen._index_nodes(kdef.body):
        ix.index = lang.BinOp(op="/", left=ix.index,
                              right=lang.Num(value=1, ctype="int"))
    return kdef


def ladder(total: int, step: int):
    units = total // step
    return [step << k for k in reversed(range(units.bit_length()))
            if units >> k & 1]


def run(kdef, arrays: dict, values: tuple, total: int, step: int,
        platform: str, keys):
    order = [p.name for p in kdef.params if p.is_pointer]
    bufs = tuple(jnp.asarray(arrays[k]) for k in order)
    offset, counts = 0, collections.Counter()
    for chunk in ladder(total, step):
        fn, info = codegen.build_kernel_fn(kdef, chunk, step, total, platform)
        bufs = jax.jit(fn, static_argnums=(3,))(offset, bufs, values, keys)
        counts.update(info.access)
        offset += chunk
    return {k: np.asarray(b) for k, b in zip(order, bufs)}, counts


def data(form: str, n: int, total: int, seed: int = 3) -> dict:
    rng = np.random.default_rng(seed)
    size = 3 * total if form == "aos" else n * n
    return {"a": rng.standard_normal(size).astype(np.float32),
            "x": rng.standard_normal(total).astype(np.float32),
            "y": rng.standard_normal(max(n, 8)).astype(np.float32)}


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("loop", ["counted", "masked"])
@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("n, total", [(320, 320), (200, 320), (256, 256)],
                         ids=["whole", "guard-bites", "blocked"])
def test_affine_forms_equal_the_gather_form_bit_for_bit(
        form, loop, platform, n, total):
    """A global range of 320 in rungs of 256 + 64; with n = 200 the guard
    holds back the last 120 lanes, whose indices lie behind the buffer."""
    src = (COUNTED if loop == "counted" else MASKED).replace(
        "IDX", FORMS[form]).replace("BOUND", BOUNDS[form])
    m = min(n, 150)
    arrays, values = data(form, n, total), (n, m)
    keys = tuple(values[i] for i in codegen.pitch_params(
        lang.parse_kernels(src)[0]))
    got, counts = run(lang.parse_kernels(src)[0], arrays, values, total, 64,
                      platform, keys)
    want, gathered = run(gather_form(src), arrays, values, total, 64,
                         platform, ())
    for k in arrays:
        assert np.array_equal(got[k].view(np.int32), want[k].view(np.int32)), k
    assert gathered["gather"] > 0 and gathered["scatter"] > 0
    assert counts["scatter"] == 0
    if n == total:  # every rung proved in bounds: nothing is gathered
        assert counts["gather"] == 0, counts
    oracle = {k: v.copy() for k, v in arrays.items()}
    Oracle(lang.parse_kernels(src)[0]).run(oracle, {"n": n, "m": m}, total)
    np.testing.assert_allclose(got["x"], oracle["x"], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("shift", [-7, 9])
def test_indices_past_either_end_clamp_and_drop_as_the_gather_form(form, shift):
    total, n, m = 320, 320, 40
    src = ENDS.replace("IDX", FORMS[form].replace("]", f" + {shift * 11}]")
                       ).replace("SHIFTED", f"a[i + {shift}]"
                                 ).replace("BOUND", BOUNDS[form])
    arrays, values = data(form, n, total), (n, m)
    # a buffer shorter than the walk: the last rows run past its end
    arrays["a"] = arrays["a"][: arrays["a"].size - 2 * n]
    keys = tuple(values[i] for i in codegen.pitch_params(
        lang.parse_kernels(src)[0]))
    got, counts = run(lang.parse_kernels(src)[0], arrays, values, total, 64,
                      "cpu", keys)
    want, _ = run(gather_form(src), arrays, values, total, 64, "cpu", ())
    for k in arrays:
        assert np.array_equal(got[k].view(np.int32), want[k].view(np.int32)), k
    oracle = {k: v.copy() for k, v in arrays.items()}
    Oracle(lang.parse_kernels(src)[0]).run(oracle, {"n": n, "m": m}, total)
    np.testing.assert_allclose(got["x"], oracle["x"], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("start, n", [(0, 384), (3, 384), (128, 256), (5, 256)])
def test_row_walk_over_the_blocked_view_from_any_column(start, n):
    """A pitch of whole blocks of 128 is walked through the ``[rows, pitch /
    128, 128]`` view: from a column known to start a block, and from one
    that is not (one block more a window)."""
    src = COUNTED.replace("IDX", "a[i * n + j]").replace(
        "int j = 0; j < BOUND", f"int j = {start}; j < n")
    arrays, values = data("row", n, n), (n, 0)
    kdef = lang.parse_kernels(src)[0]
    got, counts = run(kdef, arrays, values, n, 64, "tpu", (n,))
    want, _ = run(gather_form(src), arrays, values, n, 64, "tpu", ())
    assert np.array_equal(got["x"].view(np.int32), want["x"].view(np.int32))
    assert counts["strided"] > 0 and counts["gather"] == 0, counts
    a64 = arrays["a"].reshape(n, n)[:, start:].astype(np.float64)
    ref = arrays["x"] + a64 @ arrays["y"][start:n].astype(np.float64)
    np.testing.assert_allclose(got["x"], ref, rtol=2e-4, atol=2e-4)


# -- a compute with a global offset ------------------------------------------
# ``compute(..., global_offset=K)`` runs the items [K, K + range) through the
# launchers of the range's geometry: they lie beyond ``global_size``, so
# nothing may be proved in bounds from it (``in_range`` False: the windows
# clamp, the masked stores scatter), and the results stay the gather form's.

TAPS = """
__kernel void k(__global float* a, __global float* x, __global float* y, int n, int m) {
    int i = get_global_id(0);
    float s = 0.0f;
    for (int j = 0; j < 64; j++) {
        GUARD s += a[i + j] * y[j];
    }
    x[i] = s;
}"""
OFFSET_KERNELS = {
    **{f"{form}-{loop}": (COUNTED if loop == "counted" else MASKED).replace(
        "IDX", FORMS[form]).replace("BOUND", BOUNDS[form])
       for form in sorted(FORMS) for loop in ("counted", "masked")},
    "taps-guarded": TAPS.replace("GUARD", "if (i + j < n)"),
    "taps-clamping": TAPS.replace("GUARD", ""),
}


def text_gather_form(src: str) -> str:
    """The kernel's TEXT with every index wrapped in ``( ... ) / 1``."""
    import re

    return re.sub(r"\[([^\]]+)\]", r"[(\1) / 1]", src)


def through_compute(src: str, host: dict, values: tuple, total: int,
                    offset: int, window: int = 0) -> dict:
    import cekirdekler_tpu as ct
    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher

    arrs = {k: ClArray(v.copy(), name=k, read_only=k != "x",
                       partial_read=k == "x") for k, v in host.items()}
    group = arrs["a"].next_param(arrs["x"], arrs["y"])
    cr = NumberCruncher(ct.all_devices().subset(1), src)
    try:
        cr.enqueue_mode = bool(window)
        for _ in range(window or 1):
            group.compute(cr, 3, "k", total, 64, global_offset=offset,
                          values=values)
        if window:
            cr.barrier()
            cr.enqueue_mode = False
        keys = [k for k in cr.cores.program._cache if k[0] == "k"]
        return {k: np.array(a.host()) for k, a in arrs.items()}, keys
    finally:
        cr.dispose()


@pytest.mark.parametrize("short", [False, True], ids=["whole", "short-a"])
@pytest.mark.parametrize("kernel", sorted(OFFSET_KERNELS))
def test_a_global_offset_is_the_gather_form_bit_for_bit(kernel, short):
    """Items [128, 384) through the geometry of a range of 256: loads at a
    runtime offset, strided windows, masked stores and carried buffers; with
    ``a`` cut short the last rows and taps run past its end and clamp."""
    src = OFFSET_KERNELS[kernel]
    offset, total = 128, 256
    n, m = offset + total, 40
    form = kernel.split("-")[0]
    host = data(form if form in FORMS else "col", n, n)
    if form == "taps":
        host["a"] = host["a"][:n]
    if short:
        host["a"] = host["a"][: host["a"].size - (2 * n if form != "taps" else 9)]
    values = (n if kernel != "taps-guarded" else host["a"].size, m)
    got, keys = through_compute(src, host, values, total, offset)
    want, _ = through_compute(text_gather_form(src), host, values, total, offset)
    # built for launches that reach beyond the range, and only for those
    assert keys and all(k[5:] == ("beyond-range",) for k in keys), keys
    for k in host:
        assert np.array_equal(got[k].view(np.int32), want[k].view(np.int32)), k
    assert np.array_equal(got["x"][:offset], host["x"][:offset])  # untouched
    oracle = {k: v.copy() for k, v in host.items()}
    Oracle(lang.parse_kernels(src)[0]).run(
        oracle, {"n": values[0], "m": m}, total, offset)
    np.testing.assert_allclose(got["x"], oracle["x"], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("kernel", ["col-counted", "row-counted"])
def test_a_global_offset_in_an_enqueue_window(kernel):
    """Three computes of one window (the fused path's launchers take the
    flag from the window's own offset and size)."""
    src = OFFSET_KERNELS[kernel]
    offset, total, m = 128, 256, 40
    n = offset + total
    host = data(kernel.split("-")[0], n, n)
    got, keys = through_compute(src, host, (n, m), total, offset, window=3)
    want, _ = through_compute(text_gather_form(src), host, (n, m), total,
                              offset, window=3)
    assert keys and all(k[5:] == ("beyond-range",) for k in keys), keys
    assert np.array_equal(got["x"].view(np.int32), want["x"].view(np.int32))
    oracle = {k: v.copy() for k, v in host.items()}
    for _ in range(3):
        Oracle(lang.parse_kernels(src)[0]).run(
            oracle, {"n": n, "m": m}, total, offset)
    np.testing.assert_allclose(got["x"], oracle["x"], rtol=5e-4, atol=5e-4)


def test_a_launch_inside_the_range_keeps_the_proved_build():
    """The flag is the launch's own: offset 0 takes the build with the
    proofs (no clamp, no scatter), the same range from 128 on the one
    without, a second executable of the same kernel."""
    src = OFFSET_KERNELS["col-counted"]
    n = 384
    host = data("col", n, n)
    _, keys = through_compute(src, host, (n, 40), 256, 0)
    assert keys and all(len(k) == 5 for k in keys), keys
    kdef = lang.parse_kernels(src)[0]
    counts = {}
    for in_range in (True, False):
        fn, info = codegen.build_kernel_fn(kdef, 256, 64, 256, "tpu", in_range)
        jax.jit(fn, static_argnums=(3,)).trace(
            0, tuple(jnp.asarray(host[k]) for k in "axy"), (n, 40), (n,))
        counts[in_range] = info.access
    assert counts[True]["scatter"] == 0 and counts[True]["gather"] == 0
    # unproved: the masked store scatters (and drops what lies outside), the
    # load is still a slice, clamped element by element
    assert counts[False]["scatter"] == 1 and counts[False]["gather"] == 0
    assert counts[False]["slice"] >= 1


def test_a_buffer_read_at_a_neighbour_is_not_carried():
    """``x[i]`` rides the loop as a local only if nothing in the loop touches
    ``x`` anywhere else: with ``x[i + 1]`` read in the loop a pass must see
    what the lane next door stored in the pass before."""
    own = COUNTED.replace("IDX", "a[j * n + i]").replace("BOUND", "m")
    neighbour = own.replace("* y[j];", "* y[j] + x[i + 1];")
    n = total = 128
    arrays, values = data("col", n, total), (n, 5)
    for src, carried in ((own, 1), (neighbour, 0)):
        kdef = lang.parse_kernels(src)[0]
        got, counts = run(kdef, arrays, values, total, 64, "cpu", (n,))
        assert counts["carried"] == carried, counts
        want, _ = run(gather_form(src), arrays, values, total, 64, "cpu", ())
        assert np.array_equal(got["x"].view(np.int32), want["x"].view(np.int32))
        if carried:  # the oracle runs lanes one after the other: only the
            # kernel whose lanes do not look at each other is defined by it
            oracle = {k: v.copy() for k, v in arrays.items()}
            Oracle(kdef).run(oracle, {"n": n, "m": 5}, total)
            np.testing.assert_allclose(got["x"], oracle["x"], rtol=2e-4,
                                       atol=2e-4)


def test_through_compute_as_one_two_kernel_sequence(tmp_path):
    """The deployment's shape at n = 256 on one lane of the CPU rig: one
    compute of the kernel string, per call and in an enqueue window, against
    numpy in float64; in a profiler session the spans of its launches carry
    ``access`` and ``keys``."""
    import os

    from jax.profiler import ProfileData

    import cekirdekler_tpu as ct
    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher

    n = 256
    rng = np.random.default_rng(5)
    host = {"a": rng.standard_normal(n * n).astype(np.float32),
            "x1": np.zeros(n, np.float32), "x2": np.zeros(n, np.float32),
            "y1": rng.standard_normal(n).astype(np.float32),
            "y2": rng.standard_normal(n).astype(np.float32)}
    arrs = {k: ClArray(v, name=k, read_only=k in ("a", "y1", "y2"),
                       partial_read=k in ("x1", "x2"))
            for k, v in host.items()}
    first, *rest = arrs.values()
    group = first.next_param(*rest)
    cr = NumberCruncher(ct.all_devices().subset(1), MVT)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        group.compute(cr, 7, "mvt_kernel1 mvt_kernel2", n, 64, values=(n,))
        cr.enqueue_mode = True
        for _window in range(2):
            for _ in range(2):
                group.compute(cr, 7, "mvt_kernel1 mvt_kernel2", n, 64,
                              values=(n,))
            cr.barrier()
        cr.enqueue_mode = False
    finally:
        jax.profiler.stop_trace()
        cr.dispose()
    path = [os.path.join(r, f) for r, _d, fs in os.walk(tmp_path)
            for f in fs if f.endswith(".xplane.pb")][0]
    spans = [dict(ev.stats) for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU" for line in plane.lines
             for ev in line.events if ev.name in ("ck/launch", "ck/compile")]
    assert len(spans) >= 5
    for stats in spans:
        assert stats["access"] in (
            # both kernels behind one span, or one kernel's own compile
            "slice:1;strided:1;uniform:2;gather:0;scatter:0;carried:2",
            "slice:0;strided:1;uniform:1;gather:0;scatter:0;carried:1",
            "slice:1;strided:0;uniform:1;gather:0;scatter:0;carried:1"), stats
        assert stats["keys"] == f"n={n}"
    a64 = host["a"].reshape(n, n).astype(np.float64)
    for got, want in ((arrs["x1"].host(), 5 * (a64 @ host["y1"])),
                      (arrs["x2"].host(), 5 * (a64.T @ host["y2"]))):
        assert np.abs(got - want).max() / np.abs(want).max() < 1e-5
