"""A per-call dispatch hands its run-time scalars over in one piece (ISSUE 39).

``_KernelLauncher`` packs the offset and every plain Python / numpy value of
a C kernel into ONE vector of 32-bit words, each first converted on the host
to exactly what the launch makes of it, and the executable's entry bit-casts
them back and runs the same function.  Held here, on the CPU rig (x64, so the
language's 64-bit types are 64 bits wide):

- the host's conversion is the launch's own, bit for bit, value by value;
- the packed call's outputs equal the loose call's, bit for bit, for the
  kernels the suite builds and for one kernel a value type, with values
  chosen to bite;
- what stays out of the vector: a launcher key (static), a value that is no
  plain scalar, a cast C leaves undefined, every call from inside a trace, a
  Python kernel's values;
- a per-call ``Worker.launch`` makes ONE host-to-device scalar transfer a
  dispatch: counted by the span's ``scalars`` field and under jax's transfer
  guard;
- ``.trace`` / ``.lower`` keep their argument order, and both entries are the
  module ``jit_<kernel>``.
"""

import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cekirdekler_tpu as ct
from cekirdekler_tpu.arrays.clarray import ClArray
from cekirdekler_tpu.core.cruncher import NumberCruncher
from cekirdekler_tpu.kernel import codegen, registry
from cekirdekler_tpu.kernel.registry import KernelProgram, lowering_meta

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _src(name: str) -> str:
    with open(os.path.join(ROOT, "benchmark", "configs", name),
              encoding="utf-8") as f:
        return f.read()


MANDELBROT, NBODY = _src("mandelbrot_frame.cl"), _src("nbody_direct.cl")
MVT, SPMV = _src("polybench_mvt.cl"), _src("hpcg_spmv.cl")

#: one kernel a value type: the scalar lands in an array of its own type, so
#: the output IS the bits the kernel saw
CTYPES = {"int": np.int32, "uint": np.uint32, "float": np.float32,
          "long": np.int64, "ulong": np.uint64, "double": np.float64,
          "short": np.int16, "uchar": np.uint8, "half": np.float16}
TYPED = "\n".join(
    f"__kernel void k_{c}(__global {c}* o, {c} a) "
    "{ o[get_global_id(0)] = a; }" for c in CTYPES)


def f32(bits: int) -> np.float32:
    return np.array(bits, np.uint32).view(np.float32)[()]


def f64(bits: int) -> np.float64:
    return np.array(bits, np.uint64).view(np.float64)[()]


NAN32, NAN64 = f32(0x7FC12345), f64(0x7FF8000000ABCDEF)
#: per value type, values chosen to bite
BITING = {
    "int": [-1, -2**31, 2**31 - 1, True, np.int64(-5), np.int64(2**40 + 7),
            np.uint32(4_000_000_000), 3.9, -3.9, np.float32(-0.5),
            np.float64(2**31 - 1)],
    "uint": [4_000_000_000, -1, np.int8(-1), np.uint64(2**63 + 5), 7.99,
             np.float64(4_294_967_295.0)],
    "float": [2**24 + 1, -(2**24 + 1), np.int64(2**53 + 1), -0.0, 0.1,
              float("inf"), float("-inf"), float("nan"), NAN32,
              np.float64(1 / 3), np.float16(0.1), 1e40, -1e40, 1e-46,
              f32(0x00000001), np.uint32(4_294_967_295), True],
    "long": [2**40, -2**62, np.int32(-7), 1e18, np.uint64(2**63 + 5)],
    "ulong": [2**63 - 1, np.uint64(2**63 + 5), -1, np.float64(2.0**63)],
    "double": [2**53 + 1, -0.0, 0.1, NAN64, float("inf"), np.float32(0.1),
               NAN32, f64(0x0000000000000001)],
    "short": [-1, 40_000, np.int64(-70_000), 2.5],
    "uchar": [255, 256, -1, np.float32(200.9)],
    "half": [0.1, 70_000.0, -0.0, np.float16(float("nan")), 2049,
             np.float64(1 / 3)],
}
#: casts C leaves undefined: the value rides as it did, never packed
UNDEFINED = {"int": [float("nan"), float("inf"), 2.0**31, -1e10,
                     np.float32(3e9)],
             "uint": [-1.0, 2.0**32, float("nan")],
             "long": [1e19, float("-inf")],
             "uchar": [256.0, -0.5 - 1]}


def bits(a) -> np.ndarray:
    a = np.asarray(a)
    return a.view(f"u{a.dtype.itemsize}")


def typed(ctype: str, n: int = 128):
    prog = KernelProgram(TYPED)
    fn, info = prog.launcher(f"k_{ctype}", n // 2, 64, n, platform="cpu")
    return fn, info, (jnp.zeros(n, CTYPES[ctype]),)


def loose(fn, offset, arrays, values, keys=None):
    """Today's call: every scalar a run-time argument of its own (the
    executable hands back the arrays the kernel may replace)."""
    out = list(arrays)
    new = fn._fn(offset, tuple(arrays), tuple(values), keys)
    for i, buf in zip(fn._kept, new):
        out[i] = buf
    return tuple(out)


def ident(v) -> str:
    return f"{type(v).__name__}:{v!r}".replace(" ", "")


CASES = [(c, v) for c, vs in BITING.items() for v in vs]


# -- the host's conversion is the launch's -----------------------------------

@pytest.mark.parametrize("x64", [True, False], ids=["x64", "x32"])
@pytest.mark.parametrize("ctype, value", CASES,
                         ids=[f"{c}-{ident(v)}" for c, v in CASES])
def test_host_words_are_what_the_launch_makes_of_the_value(ctype, value, x64):
    """On the rig's 64-bit types and, as the chip runs, without them (a
    ``long`` is 32 bits wide there, and a numpy ``float64`` is a ``float32``
    before the parameter's type is asked)."""
    with jax.enable_x64(x64):
        dtype = np.dtype(codegen.ctype_to_dtype(ctype))
        try:
            seen = np.asarray(jax.jit(lambda v: jnp.asarray(v, dtype))(value))
        except OverflowError:  # a Python int beyond the widest integer
            with pytest.raises(OverflowError):
                registry._host_words(value, dtype)
            return
        assert seen.dtype == dtype
        packed = registry._host_words(value, dtype)
        if packed is None:  # out of the parameter's range at this width
            assert not x64 and dtype.kind in "iu"
            assert np.asarray(value).dtype.kind == "f"
            return
        words = np.frombuffer(packed, np.uint32)
        assert words.size == max(dtype.itemsize // 4, 1)
        back = np.asarray(jax.jit(
            lambda w: registry._from_words(w, dtype))(words))
    assert back.dtype == dtype and back.shape == ()
    assert bits(back) == bits(seen), (value, back, seen)


@pytest.mark.parametrize("ctype, value", [
    (c, v) for c, vs in UNDEFINED.items() for v in vs])
def test_a_cast_c_leaves_undefined_is_never_packed(ctype, value):
    assert registry._host_words(value, np.dtype(CTYPES[ctype])) is None
    fn, info, arrays = typed(ctype)
    got = fn(0, arrays, (value,))
    want = loose(fn, 0, arrays, (value,))
    assert info.scalars == (1, 1)  # the offset's word; the value rode loose
    assert (bits(got[0]) == bits(want[0])).all()


@pytest.mark.parametrize("value", [
    1 + 2j, "3", None, np.zeros(1, np.float32)[:1], jnp.float32(2.5)])
def test_only_plain_scalars_are_plain(value):
    assert not registry._plain(value)


@pytest.mark.parametrize("value", [
    True, 3, 2.5, np.bool_(True), np.int8(3), np.uint64(3), np.float16(2.5)])
def test_python_and_numpy_scalars_are_plain(value):
    assert registry._plain(value)


def test_a_python_int_beyond_the_widest_integer_stays_the_error_it_was():
    fn, _info, arrays = typed("int")
    with pytest.raises(OverflowError):
        loose(fn, 0, arrays, (2**70,))
    with pytest.raises(OverflowError):
        fn(0, arrays, (2**70,))


# -- packed against loose, bit for bit ---------------------------------------

@pytest.mark.parametrize("ctype, value", CASES,
                         ids=[f"{c}-{ident(v)}" for c, v in CASES])
def test_one_kernel_a_value_type_sees_the_same_bits(ctype, value):
    fn, info, arrays = typed(ctype)
    got = fn(64, arrays, (value,))
    want = loose(fn, 64, arrays, (value,))
    words = 1 + max(np.dtype(CTYPES[ctype]).itemsize // 4, 1)
    assert info.scalars == (words, 0)
    assert (bits(got[0]) == bits(want[0])).all()
    assert (bits(got[0])[:64] == 0).all()  # the offset arrived too


def _mandelbrot(n=4096):
    prog = KernelProgram(MANDELBROT)
    fn, info = prog.launcher("mandelbrot", n // 2, 64, n, platform="cpu")
    return fn, info, (jnp.full(n, -1.0, jnp.float32),)


MANDELBROT_VIEWS = {
    "python": (-2.0, -1.25, 2.5 / 64, 2.5 / 64, 64, 32),
    "numpy-of-other-dtypes": (
        np.float64(-2.0 + 1 / 3), np.float16(-1.25), np.float64(2.5 / 64),
        np.float32(2.5 / 64), np.int64(64), np.uint8(32)),
    "ints-into-floats": (-2, -1, np.float64(2.5 / 64), 2.5 / 64, 64.0, 31.9),
    "negative-zero-and-nan": (-0.0, float("nan"), 2.5 / 64, 2.5 / 64, 64, 32),
    "infinite-step": (-2.0, -1.25, float("inf"), 2.5 / 64, 64, 32),
}


@pytest.mark.parametrize("view", sorted(MANDELBROT_VIEWS))
def test_mandelbrot_renders_the_same_frame(view):
    fn, info, arrays = _mandelbrot()
    values = MANDELBROT_VIEWS[view]
    got = fn(2048, arrays, values)
    want = loose(fn, 2048, arrays, values)
    assert info.scalars == (7, 0)
    assert (bits(got[0]) == bits(want[0])).all()
    assert (np.asarray(got[0])[:2048] == -1.0).all()  # the other chunk's


@pytest.mark.parametrize("n_arg, dt", [
    (256, 0.01), (np.int64(256), np.float64(0.01)), (255.9, 2**24 + 1),
    (np.uint16(256), -0.0)])
def test_nbody_takes_the_same_step(n_arg, dt):
    n = 256
    rng = np.random.default_rng(7)
    arrays = tuple(jnp.asarray(rng.standard_normal(n).astype(np.float32))
                   for _ in range(6))
    prog = KernelProgram(NBODY)
    fn, info = prog.launcher("nBody", n, 64, n, platform="cpu")
    got = fn(0, arrays, (n_arg, dt))
    want = loose(fn, 0, arrays, (n_arg, dt))
    assert info.scalars == (3, 0)
    for g, w in zip(got, want):
        assert (bits(g) == bits(w)).all()


@pytest.mark.parametrize("kernel", ["mvt_kernel1", "mvt_kernel2"])
@pytest.mark.parametrize("pitch", [128, np.int64(128)],
                         ids=["python-int", "numpy-int"])
def test_mvt_keeps_its_keyed_pitch_out_of_the_vector(kernel, pitch):
    n = 128
    rng = np.random.default_rng(3)
    arrays = (jnp.asarray(rng.standard_normal(n * n).astype(np.float32)),
              *(jnp.asarray(rng.standard_normal(n).astype(np.float32))
                for _ in range(4)))
    prog = KernelProgram(MVT)
    fn, info = prog.launcher(kernel, n, 64, n, platform="cpu")
    got = fn(0, arrays, (pitch,))
    # the key is static, as a shape is: the offset's word alone crossed
    assert info.keyed == {"n": n} and info.scalars == (1, 0)
    assert fn._pack(0, (pitch,), (n,))[1:] == ((), ("int32", registry.KEYED))
    want = loose(fn, 0, arrays, (pitch,), (n,))
    for g, w in zip(got, want):
        assert (bits(g) == bits(w)).all()
    # a pitch that arrives as an array stays a run-time argument of its own
    got = fn(0, arrays, (jnp.int32(n),))
    assert info.keyed == {} and info.scalars == (1, 0)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5)


@pytest.mark.parametrize("alpha", [
    2.0, -0.0, float("inf"), NAN32, 2**24 + 1, np.float64(1 / 3)],
    ids=ident)
def test_spmv_scales_by_the_same_alpha(alpha):
    n, per_row = 256, 3
    rng = np.random.default_rng(11)
    rowptr = jnp.arange(0, (n + 1) * per_row, per_row, dtype=jnp.int32)
    col = jnp.asarray(rng.integers(0, n, n * per_row).astype(np.int32))
    val, x = (jnp.asarray(rng.standard_normal(k).astype(np.float32))
              for k in (n * per_row, n))
    arrays = (rowptr, col, val, x, jnp.zeros(n, jnp.float32))
    prog = KernelProgram(SPMV)
    fn, info = prog.launcher("spmv", n, 64, n, platform="cpu")
    got = fn(0, arrays, (alpha,), frozen=(0, 1, 2, 3))
    want = loose(fn, 0, arrays, (alpha,))
    assert info.scalars == (2, 0)
    assert (bits(got[4]) == bits(want[4])).all()


# -- what stays out of the vector --------------------------------------------

def test_inside_a_trace_the_launcher_takes_traced_values_and_packs_nothing():
    fn, info, arrays = _mandelbrot()
    values = MANDELBROT_VIEWS["python"]
    info.scalars = "untouched"

    @jax.jit
    def ladder(off, out, x0, max_iter):
        return fn(off, (out,), (x0,) + values[1:5] + (max_iter,))

    got = ladder(2048, arrays[0], values[0], values[5])
    assert info.scalars == "untouched"
    assert fn._packed._cache_size() == 0  # the packed entry was never built
    # baked Python values under a traced offset, as a ladder's rung is called
    baked = jax.jit(lambda off, out: fn(off, (out,), values))(2048, arrays[0])
    assert info.scalars == "untouched" and fn._packed._cache_size() == 0
    want = fn(2048, arrays, values)
    assert info.scalars == (7, 0) and fn._packed._cache_size() == 1
    for other in (got, baked):
        assert (bits(other[0]) == bits(want[0])).all()


def test_a_value_that_is_an_array_rides_beside_the_vector():
    fn, info, arrays = _mandelbrot()
    values = MANDELBROT_VIEWS["python"]
    want = fn(0, arrays, values)
    mixed = (jnp.float32(values[0]),) + values[1:5] + (np.int32(values[5]),)
    got = fn(0, arrays, mixed)
    # six words, and nothing crossed one by one: the array was on the device
    assert info.scalars == (6, 0)
    words, rest, layout = fn._pack(0, mixed, None)
    assert words.dtype == np.uint32 and words.shape == (6,)
    assert len(rest) == 1 and rest[0] is mixed[0]
    assert layout == ("int32", registry.LOOSE, "float32", "float32",
                      "float32", "int32", "int32")
    assert (bits(got[0]) == bits(want[0])).all()
    # an offset that is an array too
    got = fn(jnp.int32(0), arrays, values)
    assert info.scalars == (6, 0)
    assert (bits(got[0]) == bits(want[0])).all()


def test_a_python_kernels_values_ride_as_they_did():
    @registry.kernel
    def scale(gid, x, s=2.0):
        return x.at[gid].multiply(s)

    prog = KernelProgram(scale)
    fn, info = prog.launcher("scale", 64, 64, 128, platform="cpu")
    assert fn._packed is None
    out = fn(64, (jnp.ones(128, jnp.float32),), (3.0,))
    assert info.scalars == (0, 2)  # the offset and ``s``, each on its own
    assert lowering_meta((info,))["scalars"] == "packed:0;loose:2"
    np.testing.assert_array_equal(
        np.asarray(out[0]), np.r_[np.ones(64), np.full(64, 3.0)])


def test_a_ladder_names_no_scalars_and_dispatches_are_summed():
    fn, info, arrays = _mandelbrot()
    fn(0, arrays, MANDELBROT_VIEWS["python"])
    other = SimpleNamespace(**{**vars(info), "scalars": (5, 2)})
    ladder = SimpleNamespace(**{**vars(info), "scalars": None})
    assert lowering_meta((info, other, ladder))["scalars"] == (
        "packed:12;loose:2")
    assert "scalars" not in lowering_meta((ladder,))


# -- the inspection surface ---------------------------------------------------

def test_trace_and_lower_keep_their_argument_order_and_the_modules_name():
    prog = KernelProgram(MANDELBROT)
    fn, _info = prog.launcher("mandelbrot", 2048, 64, 4096, platform="tpu")
    buf = jax.ShapeDtypeStruct((4096,), jnp.float32)
    off = jax.ShapeDtypeStruct((), jnp.int32)
    values = tuple(jax.ShapeDtypeStruct((), t) for t in (
        jnp.float32, jnp.float32, jnp.float32, jnp.float32, jnp.int32,
        jnp.int32))
    traced = fn.trace(off, (buf,), values)
    assert [a.dtype for a in traced.jaxpr.in_avals] == [
        jnp.int32, jnp.float32] + [v.dtype for v in values]
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "module @jit_mandelbrot " in text
    assert 'kernel_name = "mandelbrot"' in text
    on_cpu, _info = prog.launcher("mandelbrot", 2048, 64, 4096, platform="cpu")
    assert "module @jit_mandelbrot " in on_cpu.lower(
        0, (jnp.zeros(4096, jnp.float32),), MANDELBROT_VIEWS["python"],
        None).as_text()
    # the entry a dispatch compiles is the same module around the same call
    words, rest, layout = fn._pack(0, MANDELBROT_VIEWS["python"], None)
    packed = fn._packed.trace(
        jax.ShapeDtypeStruct(words.shape, words.dtype), (buf,), rest, layout,
        None, {})
    assert [(a.shape, a.dtype) for a in packed.jaxpr.in_avals] == [
        ((7,), jnp.uint32), ((4096,), jnp.float32)]
    text = packed.lower(lowering_platforms=("tpu",)).as_text()
    assert "module @jit_mandelbrot " in text
    assert 'kernel_name = "mandelbrot"' in text


# -- Worker.launch: one transfer a dispatch ----------------------------------

def _launch_spans(trace_dir) -> list:
    from jax.profiler import ProfileData

    path = [os.path.join(r, f) for r, _d, fs in os.walk(trace_dir)
            for f in fs if f.endswith(".xplane.pb")][0]
    return [dict(ev.stats)
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events
            if ev.name == "ck/launch"]


def test_a_per_call_launch_makes_one_scalar_transfer_a_dispatch(
        tmp_path, monkeypatch):
    """Six value arguments and the offset: seven words in one vector.  The
    span's field counts them; the transfer guard proves that nothing else of
    the dispatch crosses: with the ONE vector put on the device explicitly
    (which the guard lets through) a launch under ``disallow`` runs, and as
    the launcher hands it over, implicitly, the guard names the vector."""
    n = 4096
    cr = NumberCruncher(ct.platforms().cpus().subset(1), MANDELBROT)
    out = ClArray(np.zeros(n, np.float32), name="frame", read=False,
                  write=True)
    values = MANDELBROT_VIEWS["python"]
    # 4096 = 64 x 64 units; 48 units a launch are two rungs (32 + 16)
    rungs = {"whole": (n, 1), "two-rungs": (48 * 64, 2)}
    try:
        for size, _n in rungs.values():
            out.compute(cr, 39, "mandelbrot", size, 64, values=values)  # warm
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for size, _n in rungs.values():
                out.compute(cr, 39, "mandelbrot", size, 64, values=values)
        finally:
            jax.profiler.stop_trace()
        spans = _launch_spans(str(tmp_path))
        assert [(s["tag"], s["scalars"]) for s in spans] == [
            ("mandelbrot x1", "packed:7;loose:0"),
            ("mandelbrot x2", "packed:14;loose:0")]

        puts = []
        pack = registry._KernelLauncher._pack

        def put_explicitly(self, offset, vals, keys):
            words, rest, layout = pack(self, offset, vals, keys)
            puts.append(words)
            return jax.device_put(words), rest, layout

        worker = cr.cores.workers[0]
        launch = worker.launch

        def guarded(*args, **kwargs):
            with jax.transfer_guard_host_to_device("disallow"):
                return launch(*args, **kwargs)

        monkeypatch.setattr(worker, "launch", guarded)
        with pytest.raises(Exception, match=r"uint32\[7\]"):
            out.compute(cr, 39, "mandelbrot", n, 64, values=values)
        cr.reset_errors()
        monkeypatch.setattr(registry._KernelLauncher, "_pack", put_explicitly)
        out.compute(cr, 39, "mandelbrot", n, 64, values=values)
        out.compute(cr, 39, "mandelbrot", 48 * 64, 64, values=values)
        assert [w.shape for w in puts] == [(7,)] * 3  # one a dispatch
    finally:
        cr.dispose()
