"""Kept views (PR 31): what a launch of the vectorized-XLA lowering derives
from a read-only array ALONE (``codegen.ViewSpec``: the ``[rows, 128]`` views
of the row gathers and run windows, a strided window's ``[rows, s]`` view) is
built when the array first meets a launcher, kept under the array object's
identity and handed to every later launch as an argument
(``registry._KeptViews``).

A kept view holds the bytes the in-launch view holds, so every case here is
held bit for bit to the in-launch form (the keeper switched off): HPCG's SpMV
and PolyBench's MVT, the benchmark's own kernels, through ``compute()`` per
call, in a window, in repeat mode (the sequence ladder) and on the fused
ladder.  The rig proves results, counts and program structure, never a time.
"""

import gc
import hashlib
import os
import re
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import cekirdekler_tpu as ct  # noqa: E402
from cekirdekler_tpu import ClArray  # noqa: E402
from cekirdekler_tpu.core.cruncher import NumberCruncher  # noqa: E402
from cekirdekler_tpu.kernel import codegen, registry  # noqa: E402
from cekirdekler_tpu.kernel.codegen import ViewSpec  # noqa: E402
from cekirdekler_tpu.kernel.registry import KernelProgram, lowering_meta  # noqa: E402

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs")


def _src(name: str) -> str:
    with open(os.path.join(CONFIGS, name + ".cl"), encoding="utf-8") as f:
        return f.read()


SPMV, MVT = _src("hpcg_spmv"), _src("polybench_mvt")
LOCAL = 64


def _csr(rng, n: int, longest: int = 40):
    """A CSR matrix of ``n`` rows, 0 to ``longest`` nonzeros each."""
    rowptr = np.zeros(n + 1, np.int32)
    rowptr[1:] = np.cumsum(rng.integers(0, longest + 1, n))
    m = int(rowptr[-1])
    return {"rowptr": rowptr, "col": rng.integers(0, n, m).astype(np.int32),
            "val": rng.standard_normal(m).astype(np.float32),
            "x": rng.standard_normal(n).astype(np.float32),
            "y": np.full(n, -1.0, np.float32)}


def _dense(rng, n: int):
    return {"a": rng.standard_normal(n * n).astype(np.float32),
            "x1": np.zeros(n, np.float32), "x2": np.zeros(n, np.float32),
            "y1": rng.standard_normal(n).astype(np.float32),
            "y2": rng.standard_normal(n).astype(np.float32)}


CASES = {
    # kernel string, host data, values, what compute() only reads / carries
    "spmv": (SPMV, "spmv", lambda rng, n: _csr(rng, n), lambda n: (1.5,),
             ("rowptr", "col", "val", "x"), ()),
    "mvt": (MVT, "mvt_kernel1 mvt_kernel2", _dense, lambda n: (n,),
            ("a", "y1", "y2"), ("x1", "x2")),
}
WANTED = {"spmv": {(1, "runs"), (2, "runs")}, "mvt": {(0, "pitch:256")}}


def _in_launch(monkeypatch):
    """The keeper switched off: every view is built in its launch, the form
    of every launch before there were kept views."""
    monkeypatch.setattr(registry._KeptViews, "views",
                        lambda self, arrays, specs: ({}, 0))


def _through_compute(case: str, mode: str, n: int = 256, seed: int = 31):
    """One lane of the CPU rig; returns the host arrays and the program."""
    src, kernels, data, values, read_only, state = CASES[case]
    host = data(np.random.default_rng(seed), n)
    arrs = {k: ClArray(v.copy(), name=k, read_only=k in read_only,
                       partial_read=k in state, write_only=k == "y")
            for k, v in host.items()}
    first, *rest = arrs.values()
    group = first.next_param(*rest)
    cr = NumberCruncher(ct.platforms().cpus().subset(1), src)
    stats = {}
    try:
        def compute():
            group.compute(cr, 31, kernels, n, LOCAL, values=values(n))

        if mode == "per_call":
            compute()
            compute()
        elif mode == "repeat":  # the sequence ladder, x3 on the device
            cr.cores.repeat_count = 3
            compute()
            compute()
        else:  # a window of two computes never engages the fused ladder
            cr.enqueue_mode = True
            for _window in range(1 if mode == "window" else 2):
                for _ in range(2 if mode == "window" else 6):
                    compute()
                cr.barrier()
            cr.enqueue_mode = False
        stats["fused_iters"] = cr.fused_stats["fused_iters"]
        keeper = cr.cores.program.kept_views
        stats["kept"] = {(k[1]) for k in keeper._kept}
        stats["bytes"] = keeper.bytes_kept()
        return {k: a.host().copy() for k, a in arrs.items()}, stats
    finally:
        cr.dispose()


@pytest.mark.parametrize("mode", ["per_call", "window", "repeat", "fused"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_outputs_equal_the_in_launch_form_bit_for_bit(case, mode, monkeypatch):
    got, stats = _through_compute(case, mode)
    assert stats["kept"] == {kind for _p, kind in WANTED[case]}, stats
    assert stats["bytes"] > 0
    assert (stats["fused_iters"] > 0) == (mode == "fused"), stats
    _in_launch(monkeypatch)
    want, none = _through_compute(case, mode)
    assert none["kept"] == set() and none["bytes"] == 0
    assert none["fused_iters"] == stats["fused_iters"]
    for k in got:
        assert got[k].tobytes() == want[k].tobytes(), k
    if case == "spmv":  # and it is the product
        d = _csr(np.random.default_rng(31), 256)
        ref = np.array([1.5 * np.sum(d["val"][a:b].astype(np.float64)
                                     * d["x"][d["col"][a:b]])
                        for a, b in zip(d["rowptr"][:-1], d["rowptr"][1:])])
        assert np.abs(got["y"] - ref).max() < 1e-4


def _device(host: dict) -> tuple:
    return tuple(jnp.asarray(v) for v in host.values())


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_launches_over_the_same_arrays_build_each_view_once(platform):
    """``built`` 3 (2 on a CPU lane: no row gather of ``x``) then 0; the four
    rungs of a product are four launchers over ONE view of each table, and
    what they return for the arrays they only read are the very objects."""
    n = 1024
    prog = KernelProgram(SPMV)
    bufs = _device(_csr(np.random.default_rng(1), n))
    specs = {ViewSpec(1, "runs"), ViewSpec(2, "runs")} | (
        {ViewSpec(3, "rows")} if platform == "tpu" else set())
    built = []
    for chunk in (512, 256, 128, 64):
        fn, info = prog.launcher("spmv", chunk, LOCAL, n, platform=platform)
        for _ in range(3):
            out = fn(0, bufs, (np.float32(2.0),))
            assert [o is b for o, b in zip(out, bufs)] == [True] * 4 + [False]
            built.append(info.views_built)
            assert info.views_kept == len(specs)
            assert lowering_meta((info,))["views"] == (
                f"kept:{len(specs)};built:{built[-1]}")
        assert set(info.views) == specs
    assert built == [len(specs)] + [0] * 11
    assert len(prog.kept_views._kept) == len(specs)
    # the views' bytes: twice a table for its run view, the plain one's once
    tables = 2 * 2 * (bufs[1].size + 256) * 4
    assert prog.kept_views.bytes_kept() == pytest.approx(tables, rel=0.05) \
        if platform == "cpu" else prog.kept_views.bytes_kept() > tables


def test_a_kept_view_holds_the_in_launch_views_bytes():
    buf = jnp.asarray(np.random.default_rng(2).standard_normal(1000), jnp.float32)
    ctx = codegen._Ctx(8, 0, 8, 8, {})
    ctx.bufs["t"] = buf
    for kind, view in (("rows", ctx.rows_view("t")),
                       ("runs", ctx.rows_view("t", overlapping=True))):
        spec = ViewSpec(0, kind)
        kept = spec.build(buf)
        assert np.asarray(kept).tobytes() == np.asarray(view).tobytes()
        # what the device holds: whole tiles of (8, 128)
        assert 0 <= spec.nbytes(buf.shape, 4) - kept.size * 4 < 8 * 128 * 4
    pitch = ViewSpec(0, "pitch:40")
    assert pitch.build(buf).shape == (25, 40)
    assert pitch.nbytes(buf.shape, 4) == 32 * 128 * 4  # in tiles of (8, 128)


def test_a_re_upload_is_seen_and_the_old_view_freed():
    """``fresh_call``'s path: new values in the arrays a kernel only reads
    arrive as new device arrays; their views are built anew (the product is
    the new values') and the old ones go with the old arrays."""
    n = 256
    host = _csr(np.random.default_rng(3), n)
    arrs = {k: ClArray(v.copy(), name=k, read_only=k != "y", write_only=k == "y")
            for k, v in host.items()}
    first, *rest = arrs.values()
    group = first.next_param(*rest)
    cr = NumberCruncher(ct.platforms().cpus().subset(1), SPMV)
    keeper = cr.cores.program.kept_views
    try:
        def product():
            group.compute(cr, 5, "spmv", n, LOCAL, values=(1.0,))
            d = {k: np.asarray(a.host()) for k, a in arrs.items()}
            return d["y"].copy(), np.array([
                np.sum(d["val"][a:b].astype(np.float64) * d["x"][d["col"][a:b]])
                for a, b in zip(d["rowptr"][:-1], d["rowptr"][1:])])

        y, ref = product()
        assert np.abs(y - ref).max() < 1e-4
        ids, nbytes = set(keeper._kept), keeper.bytes_kept()
        assert len(ids) == 2 and nbytes > 0
        arrs["val"].host()[:] = -3.0 * arrs["val"].host()
        arrs["col"].host()[:] = arrs["col"].host()[::-1].copy()
        y2, ref2 = product()
        assert np.abs(y2 - ref2).max() < 1e-4 and np.abs(y2 - y).max() > 0.1
        gc.collect()
        assert len(keeper._kept) == 2 and keeper.bytes_kept() == nbytes
        assert not ids & set(keeper._kept)  # other objects' views
    finally:
        cr.dispose()


GATHER_AND_STORE = """
__kernel void k(__global int* ix, __global float* t, __global float* y) {
    int i = get_global_id(0);
    y[i] = t[ix[i]];
    t[i] = t[i] + 1.0f;
}
__kernel void reads(__global int* ix, __global float* t, __global float* y) {
    int i = get_global_id(0);
    y[i] = t[ix[i]] * 2.0f;
}
__kernel void bumps(__global int* ix, __global float* t, __global float* y) {
    int i = get_global_id(0);
    t[i] = t[i] + 1.0f;
}
"""


def test_a_kernel_that_stores_to_the_table_it_gathers_from_keeps_no_view():
    n = 512
    rng = np.random.default_rng(4)
    ix, t = rng.integers(0, n, n).astype(np.int32), rng.standard_normal(n).astype(np.float32)
    prog = KernelProgram(GATHER_AND_STORE)
    fn, info = prog.launcher("k", n, LOCAL, n, platform="tpu")
    bufs = (jnp.asarray(ix), jnp.asarray(t), jnp.zeros(n, jnp.float32))
    for _ in range(3):
        bufs = fn(0, bufs, ())
    assert info.views == () and info.views_kept == 0
    assert not prog.kept_views._kept
    # pass 3 gathered what passes 1 and 2 left
    one = np.float32(1.0)
    assert np.array_equal(np.asarray(bufs[2]), ((t + one) + one)[ix])
    assert np.array_equal(np.asarray(bufs[1]), ((t + one) + one) + one)


def test_a_table_another_kernel_of_the_launch_stores_to_keeps_no_view():
    """``reads`` alone keeps its table's view; in one launch with ``bumps``
    the table comes back replaced every call, and nothing is kept of it."""
    n = 512
    rng = np.random.default_rng(5)
    host = {"ix": rng.integers(0, n, n).astype(np.int32),
            "t": rng.standard_normal(n).astype(np.float32),
            "y": np.zeros(n, np.float32)}
    prog = KernelProgram(GATHER_AND_STORE)
    assert prog.frozen(("reads",)) == {0, 1}
    assert prog.frozen(("reads", "bumps")) == {0}
    fn, info = prog.launcher("reads", n, LOCAL, n, platform="tpu")
    bump, _ = prog.launcher("bumps", n, LOCAL, n, platform="cpu")
    bufs = _device(host)
    frozen = prog.frozen(("reads", "bumps"))
    t = host["t"].copy()
    for _ in range(3):
        bufs = bump(0, fn(0, bufs, (), frozen=frozen), (), frozen=frozen)
        assert (info.views_kept, info.views_built) == (0, 0)
        assert np.array_equal(np.asarray(bufs[2]), np.float32(2.0) * t[host["ix"]])
        t = t + np.float32(1.0)
    assert not prog.kept_views._kept
    fn(0, bufs, ())  # alone: the kernel's own stores decide
    assert (info.views_kept, info.views_built) == (1, 1)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_a_view_over_the_memory_rule_is_built_in_the_launch(monkeypatch):
    """A device that reports a limit keeps views up to ``SHARE`` of it: the
    first table's fits, the second's would pass it and stays a temporary of
    the launch (its ``concatenate`` is in the launcher's program), with the
    same product."""
    n = 1024
    host = _csr(np.random.default_rng(6), n)
    one = ViewSpec(1, "runs").nbytes(host["col"].shape, 4)
    prog = KernelProgram(SPMV)
    fn, info = prog.launcher("spmv", n, LOCAL, n, platform="cpu")
    bufs = _device(host)
    want = np.asarray(fn(0, bufs, (np.float32(1.0),))[4])
    assert info.views_kept == 2

    monkeypatch.setattr(registry._KeptViews, "limit",
                        staticmethod(lambda device: int(1.5 * one / 0.25)))
    tight = KernelProgram(SPMV)
    fn, info = tight.launcher("spmv", n, LOCAL, n, platform="cpu")
    for built in (1, 0):
        got = np.asarray(fn(0, bufs, (np.float32(1.0),))[4])
        assert (info.views_kept, info.views_built) == (1, built)
        assert got.tobytes() == want.tobytes()
    assert tight.kept_views.bytes_kept() == one
    specs = (ViewSpec(1, "runs"), ViewSpec(2, "runs"))
    concatenates = [
        sum(e.primitive.name == "concatenate" for e in _eqns(
            jax.make_jaxpr(lambda v: fn(0, bufs, (np.float32(1.0),), v))(
                {s: s.build(bufs[s.param]) for s in handed}).jaxpr))
        for handed in (specs[:0], specs[:1], specs)]
    assert np.diff(concatenates).tolist() == [-1, -1], concatenates


def test_the_row_walk_reads_the_kept_2d_view_and_the_blocked_one_without():
    """MVT's kernel 1 over a kept ``[rows, n]`` view cuts its window as one
    2-D slice of whole tiles; handed no view (a table the launch stores to, a
    view over the memory rule) it keeps the blocked ``[rows, n / 128, 128]``
    view, which costs a launch nothing.  Kernel 2 asks for no view."""
    n = 256
    prog = KernelProgram(MVT)
    bufs = _device(_dense(np.random.default_rng(7), n))
    fn, info = prog.launcher("mvt_kernel1", n, LOCAL, n, platform="tpu")
    spec = ViewSpec(0, f"pitch:{n}")
    assert fn.wants(bufs, (n,), (n,)) == (spec,)

    def window_operands(views):
        jaxpr = jax.make_jaxpr(lambda v: fn(0, bufs, (n,), v))(views).jaxpr
        return {e.invars[0].aval.shape for e in _eqns(jaxpr)
                if e.primitive.name == "dynamic_slice"
                and e.invars[0].aval.ndim > 1 and e.outvars[0].aval.size > n}

    assert window_operands({spec: spec.build(bufs[0])}) == {(n, n)}
    assert window_operands({}) == {(n, n // 128, 128)}
    fn2, info2 = prog.launcher("mvt_kernel2", n, LOCAL, n, platform="tpu")
    fn2(0, bufs, (n,))
    assert info2.views == () and (info2.views_kept, info2.views_built) == (0, 0)


@pytest.mark.parametrize("donate", [False, True])
def test_the_fused_ladder_holds_what_has_a_view_and_closes_over_it(donate):
    """The fused executable takes the held arrays and the views beside the
    buffers that move: its loop carries (and returns, and donates) only
    those; what it hands back for a held array is the object that went in,
    so dispatch after dispatch builds nothing."""
    n = 512
    prog = KernelProgram(SPMV)
    bufs = _device(_csr(np.random.default_rng(8), n))
    fused = prog.fused_launcher(("spmv",), LOCAL, n, LOCAL, n, (2.0,),
                                platform="cpu", donate=donate)
    one, _ = KernelProgram(SPMV).launcher("spmv", n, LOCAL, n, platform="cpu")
    want = np.asarray(one(0, bufs, (2.0,))[4])
    out = bufs
    for built in (2, 0, 0):
        # as Worker.launch_fused does: the outputs are the next inputs (a
        # donating executable has deleted what moved through it)
        out = fused(0, n // LOCAL - 1, 2, out)
        assert (fused.info.views_kept, fused.info.views_built) == (2, built)
        assert out[1] is bufs[1] and out[2] is bufs[2]  # held
    rows = (n // LOCAL - 1) * LOCAL
    assert np.asarray(out[4])[:rows].tobytes() == want[:rows].tobytes()
    # the program: 3 buffers move (rowptr, x, y), 2 are held, 2 views
    views = {s: s.build(out[s.param]) for s in fused.info.rungs[0].views}
    jaxpr = jax.make_jaxpr(fused._fn)(
        0, 7, 2, (out[0], out[3], out[4]), {1: out[1], 2: out[2]}, views)
    assert len(jaxpr.out_avals) == 3
    outer = next(e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "while")
    # its loop carries counters and what a pass replaces: no view, no table
    carried = [v.aval.shape for v in outer.outvars if v.aval.ndim]
    assert carried == [(n,)], carried


# what 4f17af4 (this PR's parent) built for the benchmark's Pallas cells'
# kernels: sha1 of the launchers' jaxprs, addresses taken out (traced with
# 64-bit types on, as the rig runs)
PARENTS = {
    "mandelbrot cpu launcher": "a6526352b862c135",
    "mandelbrot cpu fused": "be98ccfc8261f0f3",
    "mandelbrot cpu seq": "81820d64d29d6a67",
    "mandelbrot tpu launcher": "4199e39f988fd3a4",
    "mandelbrot tpu fused": "19582de4cbd603ea",
    "mandelbrot tpu seq": "c8589332b2cb8fcc",
    "nBody cpu launcher": "68c7f8e9aea41dfd",
    "nBody cpu fused": "3299656a26007511",
    "nBody cpu seq": "120d091372dca477",
    "nBody tpu launcher": "a9093a7fefc7533f",
    "nBody tpu fused": "b89677c03667b39f",
    "nBody tpu seq": "4e7184aeee23fa44",
}


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("config,name", [("mandelbrot_frame", "mandelbrot"),
                                         ("nbody_direct", "nBody")])
def test_kernels_without_views_build_the_parents_programs(config, name, platform):
    """mandelbrot and n-body ask for no view on either lowering: their
    launchers, their fused ladder and their sequence ladder trace to the
    jaxprs of the parent commit, byte for byte."""
    def sha(jaxpr) -> str:
        return hashlib.sha1(re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr)).encode()
                            ).hexdigest()[:16]

    prog = KernelProgram(_src(config))
    kdef, n = prog._c_kernels[name], 2048
    arrays = tuple(jnp.zeros(n, jnp.int32 if p.ctype == "int" else jnp.float32)
                   for p in kdef.params if p.is_pointer)
    vals = tuple(np.float32(0.5) if p.ctype == "float" else 7
                 for p in kdef.params if not p.is_pointer)
    fn, info = prog.launcher(name, 1024, 256, n, platform=platform)
    assert info.lowering == ("pallas" if platform == "tpu" else "xla")
    fused = prog.fused_launcher((name,), 256, n, 256, n, vals, platform=platform)
    seq = prog.sequence_launcher((name,), (1024, 512), 256, n, 3, None, vals,
                                 platform=platform)
    got = {
        "launcher": sha(jax.make_jaxpr(lambda o, a: fn(o, a, vals))(0, arrays)),
        "fused": sha(jax.make_jaxpr(lambda o, u, i, b: fused(o, u, i, b))(
            0, 5, 3, arrays)),
        "seq": sha(jax.make_jaxpr(lambda o, b: seq(o, b))(0, arrays)),
    }
    assert got == {k: PARENTS[f"{name} {platform} {k}"] for k in got}
    assert info.views == () and fn.wants(arrays, vals, None) == ()
    assert lowering_meta((info,))["views"] == "kept:0;built:0"


# what 2a448f8 (PR 34's parent) built for the benchmark's other kernels:
# HPCG's SpMV, PolyBench's MVT and the wave membrane, whose cells start
# every window per call (values that change, a window that never fused, a
# read across lanes) and must run the programs they ran.  The launchers'
# in-launch form (a trace is handed no kept view), as above
PARENTS_34 = {
    "spmv cpu launcher": "960cafd0368bc7d5",
    "spmv cpu fused": "c1a1ce6a9293cc5c",
    "spmv tpu launcher": "5de83af6e83ccfd1",
    "spmv tpu fused": "d3b6c07ede26d3ce",
    "mvt_kernel1 cpu launcher": "b148f9daac8fef1f",
    "mvt_kernel2 cpu launcher": "b023b69ea1ea3b06",
    "mvt cpu fused": "e0956c045c3805cb",
    "mvt_kernel1 tpu launcher": "b148f9daac8fef1f",
    "mvt_kernel2 tpu launcher": "b023b69ea1ea3b06",
    "mvt tpu fused": "e0956c045c3805cb",
    "waveStep cpu launcher": "86161f39a88f9b43",
    "rotate cpu launcher": "ea05b7936af9a625",
    "wave cpu fused": "02a452d9e0de0415",
    "waveStep tpu launcher": "86161f39a88f9b43",
    "rotate tpu launcher": "b523fddfacf6d929",
    "wave tpu fused": "d8e6f02d15a93a3a",
}
OTHER_CELLS = {
    # configuration, kernels, array sizes for n items, values, n
    "spmv": ("hpcg_spmv", ("spmv",),
             lambda n: (n + 1, 8 * n, 8 * n, n, n), (1.5,), 2048),
    "mvt": ("polybench_mvt", ("mvt_kernel1", "mvt_kernel2"),
            lambda n: (n * n, n, n, n, n), (256,), 256),
    "wave": ("wave_membrane", ("waveStep", "rotate"),
             lambda n: (n, n, n), (64, 32, 0.22), 2048),
}


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("case", sorted(OTHER_CELLS))
def test_the_other_cells_kernels_build_the_parents_programs(case, platform):
    """SpMV, MVT and the wave step: every kernel's launcher and the
    sequence's fused ladder trace to the jaxprs of PR 34's parent, byte for
    byte, on both lowerings."""
    def sha(jaxpr) -> str:
        return hashlib.sha1(re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr)).encode()
                            ).hexdigest()[:16]

    config, names, sizes, vals, n = OTHER_CELLS[case]
    prog = KernelProgram(_src(config))
    got = {}
    for name in names:
        pointers = [p for p in prog._c_kernels[name].params if p.is_pointer]
        arrays = tuple(
            jnp.zeros(size, jnp.int32 if p.ctype == "int" else jnp.float32)
            for size, p in zip(sizes(n), pointers))
        fn, _info = prog.launcher(name, n // 2, LOCAL, n, platform=platform)
        got[f"{name} {platform} launcher"] = sha(
            jax.make_jaxpr(lambda o, a: fn(o, a, vals))(0, arrays))
    fused = prog.fused_launcher(names, LOCAL, n, LOCAL, n, vals,
                                platform=platform)
    got[f"{case} {platform} fused"] = sha(
        jax.make_jaxpr(lambda o, u, i, b: fused(o, u, i, b))(0, 5, 3, arrays))
    assert got == {k: PARENTS_34[k] for k in got}


def test_lanes_that_meet_an_array_together_build_its_view_once():
    """More threads than cores call four rung launchers over the same tables
    and over tables of their own: every (array, kind) is built exactly once
    and every product is right."""
    import sys

    n, threads = 512, 12
    prog = KernelProgram(SPMV)
    shared = _device(_csr(np.random.default_rng(9), n))
    fns = [prog.launcher("spmv", c, LOCAL, n, platform="cpu") for c in (256, 128)]
    want = np.asarray(fns[0][0](0, shared, (1.0,))[4])[:128].tobytes()
    prog.kept_views._kept.clear()
    prog.kept_views._bytes.clear()
    built, errors, go = [], [], threading.Barrier(threads)

    def lane(k: int) -> None:
        try:
            own = _device(_csr(np.random.default_rng(100 + k), n))
            go.wait(timeout=60)
            for bufs in (shared, own, shared):
                for fn, info in fns:
                    out = fn(0, bufs, (1.0,))
                    if bufs is shared:
                        assert np.asarray(out[4])[:128].tobytes() == want
            built.append(len([key for key in prog.kept_views._kept
                              if key[0] in (id(own[1]), id(own[2]))]))
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pool = [threading.Thread(target=lane, args=(k,)) for k in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(old)
    assert not errors, errors
    assert built == [2] * threads
    gc.collect()
    # the threads' own tables are gone, and their views with them
    assert {key[0] for key in prog.kept_views._kept} == {id(shared[1]), id(shared[2])}
    one = ViewSpec(1, "runs").nbytes(shared[1].shape, 4)
    assert prog.kept_views.bytes_kept() == 2 * one
