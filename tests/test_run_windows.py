"""Run windows and row gathers of the vectorized-XLA lowering (PR 26, 27).

A ``for`` whose variable goes up by one a pass and indexes read-only
buffers as ``T[j]`` fetches each lane's run of consecutive elements once
for ``_RUN_WINDOW`` passes (kernel/codegen.py ``_run_window``: one row of a
half-overlapping row view a lane, moved up by the run's offset in six
select stages) where the plain lowering gathers a chunk-wide element in
every pass; on a TPU lane a per-lane gather reads whole 128-wide rows
(``_take_rows``).  Both are other routes to the SAME values: every case
here is held bit for bit to the plain gather lowering (the run analysis
switched off) and to numpy.
"""

import os

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from cekirdekler_tpu.kernel import codegen  # noqa: E402
from cekirdekler_tpu.kernel.registry import KernelProgram  # noqa: E402

N = 512
LOCAL = 64

CSR = """
__kernel void k(__global int* lo, __global int* col, __global float* val,
                __global float* x, __global float* y) {
    int i = get_global_id(0);
    float s = 0.0f;
    for (int j = lo[i]; j < lo[i + 1]; j++) { s += val[j] * x[col[j]]; }
    y[i] = s;
}
"""
SHIFTED = """
__kernel void k(__global float* a, __global float* y) {
    int i = get_global_id(0);
    float s = 0.0f;
    for (int j = i - 3; j < i + 40; j += 1) { s += a[j]; }
    y[i] = s;
}
"""
BREAKS = """
__kernel void k(__global int* lo, __global float* a, __global float* y) {
    int i = get_global_id(0);
    float s = 0.0f;
    for (int j = lo[i]; j < lo[i + 1]; j++) {
        if (a[j] < -1.5f) { break; }
        if (a[j] < 0.0f) { continue; }
        s += a[j];
    }
    y[i] = s;
}
"""
NESTED = """
__kernel void k(__global int* lo, __global int* cnt, __global float* a,
                __global float* y) {
    int i = get_global_id(0);
    float s = 0.0f;
    for (int j = lo[i]; j < lo[i + 1]; j++) {
        for (int r = 0; r < cnt[j]; r++) { s += a[j] * 0.5f; }
    }
    y[i] = s;
}
"""
STORED = """
__kernel void k(__global int* lo, __global float* a, __global float* y) {
    int i = get_global_id(0);
    float s = 0.0f;
    for (int j = lo[i]; j < lo[i + 1]; j++) { s += a[j]; y[i] = s + y[i]; }
}
"""
COND_READ = """
__kernel void k(__global int* lo, __global int* stop, __global float* a,
                __global float* y) {
    int i = get_global_id(0);
    float s = 0.0f;
    for (int j = lo[i]; stop[j] == 0; j++) { s += a[j]; }
    y[i] = s;
}
"""


def _rows(rng, n, longest):
    """Row pointers of ``n`` rows, 0 to ``longest`` elements each."""
    lens = rng.integers(0, longest + 1, n)
    lo = np.zeros(n + 1, np.int32)
    lo[1:] = np.cumsum(lens)
    return lo


def _case(name, rng):
    if name == "csr":
        lo = _rows(rng, N, 70)  # rows past two refills of 32
        m = int(lo[-1])
        return CSR, [lo, rng.integers(0, N, m).astype(np.int32),
                     rng.standard_normal(m).astype(np.float32),
                     rng.standard_normal(N).astype(np.float32),
                     np.zeros(N, np.float32)]
    if name == "shifted":  # runs that start before the buffer and end after it
        return SHIFTED, [rng.standard_normal(N).astype(np.float32),
                         np.zeros(N, np.float32)]
    if name == "breaks":
        lo = _rows(rng, N, 45)
        return BREAKS, [lo, rng.standard_normal(int(lo[-1])).astype(np.float32),
                        np.zeros(N, np.float32)]
    if name == "nested":
        lo = _rows(rng, N, 9)
        m = int(lo[-1])
        return NESTED, [lo, rng.integers(0, 4, m).astype(np.int32),
                        rng.standard_normal(m).astype(np.float32),
                        np.zeros(N, np.float32)]
    if name == "stored":
        lo = _rows(rng, N, 5)
        return STORED, [lo, rng.standard_normal(int(lo[-1])).astype(np.float32),
                        np.ones(N, np.float32)]
    if name == "cond_read":
        lo = _rows(rng, N, 37)
        m = int(lo[-1])
        stop = np.zeros(m + 1, np.int32)
        stop[lo[1:][lo[1:] > lo[:-1]] - 1] = 1  # at a row's last element
        stop[m] = 1
        return COND_READ, [lo, stop, rng.standard_normal(m + 1).astype(np.float32),
                           np.zeros(N, np.float32)]
    raise AssertionError(name)


def _run(src, arrays, platform, monkeypatch=None, plain=False):
    if plain:
        monkeypatch.setattr(codegen, "_run_reads", lambda *a: (None, []))
    fn, _info = KernelProgram(src).launcher("k", N, LOCAL, N, platform=platform)
    out = fn(0, tuple(jnp.asarray(a) for a in arrays), ())
    return [np.asarray(o) for o in out]


CASES = ["csr", "shifted", "breaks", "nested", "stored", "cond_read"]


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("name", CASES)
def test_run_windows_equal_the_plain_gathers(name, platform, monkeypatch):
    """The launcher of a CPU lane (run windows) and the one a TPU lane gets
    when Pallas refuses (run windows and row gathers), run here on the CPU,
    against the lowering with the run analysis off: bit for bit."""
    src, arrays = _case(name, np.random.default_rng(7))
    got = _run(src, arrays, platform)
    want = _run(src, arrays, "cpu", monkeypatch, plain=True)
    for g, w in zip(got, want):
        assert g.tobytes() == w.tobytes()


def test_csr_product_against_numpy():
    src, (lo, col, val, x, y) = _case("csr", np.random.default_rng(11))
    got = _run(src, [lo, col, val, x, y], "cpu")[4]
    want = np.array([np.sum(val[a:b].astype(np.float64) * x[col[a:b]])
                     for a, b in zip(lo[:-1], lo[1:])])
    assert np.abs(got - want).max() < 1e-4


@pytest.mark.parametrize("name,tables", [
    ("csr", ["col", "val"]), ("shifted", ["a"]), ("breaks", ["a"]),
    ("nested", ["a", "cnt"]), ("stored", ["a"]), ("cond_read", ["a", "stop"]),
])
def test_which_reads_are_runs(name, tables, monkeypatch):
    """``T[j]`` of the loop's own variable on a buffer the loop does not
    store to; the inner loop of ``nested`` (uniform variable) has none."""
    seen = []
    real = codegen._run_reads

    def spy(*a):
        out = real(*a)
        seen.append(out)
        return out

    monkeypatch.setattr(codegen, "_run_reads", spy)
    src, arrays = _case(name, np.random.default_rng(3))
    _run(src, arrays, "cpu")
    found = [t for _j, ts in seen for t in ts]
    assert sorted(set(found)) == tables
    assert all(j in (None, "j") or not ts for j, ts in seen)


@pytest.mark.parametrize("src_step", ["j += 2", "j--", "j = j + 1"])
def test_other_steps_keep_the_gather(src_step, monkeypatch):
    """Only ``j++`` / ``j += 1`` is recognised; any other step keeps the
    per-pass gather."""
    src = SHIFTED.replace("j += 1", src_step).replace("j < i + 40", "j != i + 41")
    src = src.replace("int j = i - 3", "int j = i + 1")
    if src_step == "j--":
        src = src.replace("j != i + 41", "j > i - 9")
    seen = []
    real = codegen._run_reads
    monkeypatch.setattr(codegen, "_run_reads",
                        lambda *a: seen.append(real(*a)) or seen[-1])
    arrays = [np.arange(N, dtype=np.float32), np.zeros(N, np.float32)]
    _run(src, arrays, "cpu")
    assert seen and all(ts == [] for _j, ts in seen), seen


@pytest.mark.parametrize("n", [1, 5, 127, 128, 129, 1000, 4096])
@pytest.mark.parametrize("chunk", [1 << 18, 256])
def test_window_and_row_gather_against_numpy(n, chunk, monkeypatch):
    """``_run_window`` and ``_take_rows`` alone: any buffer length, starts
    from far before the buffer to far after it (the gather's clamp), lanes
    in one piece and in chunks (with a ragged last chunk)."""
    monkeypatch.setattr(codegen, "_LANE_CHUNK", chunk)
    rng = np.random.default_rng(n)
    for dtype in (np.float32, np.int32):
        buf = (rng.standard_normal(n) * 100).astype(dtype)
        if dtype is np.float32 and n > 2:
            buf[:2] = [-0.0, np.nan]  # bits come back as stored
        ctx = codegen._Ctx(8, 0, 8, 8, {})
        ctx.bufs["t"] = jnp.asarray(buf)
        j0 = rng.integers(-300, n + 300, 700).astype(np.int32)
        got = np.asarray(codegen._run_window(ctx, "t", jnp.asarray(j0)))
        at = np.clip(j0[None, :] + np.arange(codegen._RUN_WINDOW)[:, None], 0, n - 1)
        assert got.shape == at.shape and got.tobytes() == buf[at].tobytes()
        one = np.asarray(codegen._take_rows(ctx, "t", jnp.asarray(j0)))
        assert one.tobytes() == buf[np.clip(j0, 0, n - 1)].tobytes()


# --- the refill itself (PR 27): one row a lane of the overlapping view ------

TWO_TABLES = """
__kernel void k(__global int* lo, __global int* cnt, __global int* ti,
                __global float* tf, __global float* y) {
    int i = get_global_id(0);
    float s = 0.0f;
    for (int j = lo[i]; j < lo[i] + cnt[i]; j++) { s += tf[j] * 0.5f + ti[j]; }
    y[i] = s;
}
"""
OFFSETS = [0, 31, 32, 63, 64, 96, 127]  # of a run's start in its 128-block


def _starts(m):
    """Every offset in every 128-block from two before the table to two
    after it: runs that begin before element 0, straddle each boundary of
    the view's rows and end past the last element."""
    blocks = np.arange(-2, m // 128 + 3)
    return (blocks[:, None] * 128 + np.array(OFFSETS)[None, :]).ravel()


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("passes", [5, 33, 70])
@pytest.mark.parametrize("m", [1, 63, 64, 65, 127, 129, 1000])
def test_refill_in_a_loop_of_two_tables(m, passes, platform, monkeypatch):
    """An ``int`` and a ``float`` table of ``m`` elements walked by one
    loop for ``passes`` passes (33: a second refill, 70: a third, each
    starting 32 after the one before it, at any offset), the runs starting
    at every offset of every block around the table."""
    rng = np.random.default_rng(m * 100 + passes)
    lo = np.resize(_starts(m), N).astype(np.int32)
    cnt = np.full(N, passes, np.int32)
    cnt[::5] = rng.integers(0, passes + 1, len(cnt[::5]))
    arrays = [lo, cnt, rng.integers(-1000, 1000, m).astype(np.int32),
              (rng.standard_normal(m) * 100).astype(np.float32),
              np.zeros(N, np.float32)]
    got = _run(TWO_TABLES, arrays, platform)
    want = _run(TWO_TABLES, arrays, "cpu", monkeypatch, plain=True)
    for g, w in zip(got, want):
        assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_refill_in_lane_chunks(platform, monkeypatch):
    """The same loop with the lanes' rows materialized 256 at a time."""
    monkeypatch.setattr(codegen, "_LANE_CHUNK", 256)
    rng = np.random.default_rng(5)
    m = 700
    arrays = [np.resize(_starts(m), N).astype(np.int32),
              rng.integers(0, 71, N).astype(np.int32),
              rng.integers(-1000, 1000, m).astype(np.int32),
              rng.standard_normal(m).astype(np.float32),
              np.zeros(N, np.float32)]
    got = _run(TWO_TABLES, arrays, platform)
    want = _run(TWO_TABLES, arrays, "cpu", monkeypatch, plain=True)
    for g, w in zip(got, want):
        assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("offset", OFFSETS)
def test_window_from_each_offset_of_a_block(offset, dtype):
    """``_run_window`` alone, every lane's run at the same offset of its
    block: offsets under 64 read a row of the view's first half, the others
    one of its second; 31 / 32 and 63 / 64 are the last and first offsets
    of a stage's bit; the first blocks lie before the table, the last
    after it."""
    n = 1000
    buf = (np.random.default_rng(offset).standard_normal(n) * 100).astype(dtype)
    ctx = codegen._Ctx(8, 0, 8, 8, {})
    ctx.bufs["t"] = jnp.asarray(buf)
    j0 = (np.arange(-3, n // 128 + 3) * 128 + offset).astype(np.int32)
    got = np.asarray(codegen._run_window(ctx, "t", jnp.asarray(j0)))
    at = np.clip(j0[None, :] + np.arange(codegen._RUN_WINDOW)[:, None], 0, n - 1)
    assert got.tobytes() == buf[at].tobytes()


@pytest.mark.parametrize("n", [1, 63, 64, 65, 127, 128, 129, 1000])
def test_overlapping_view_rows(n):
    """Row ``k`` of the view's first half holds ``[128 k, 128 k + 128)`` of
    the table padded by 128 clamped elements in front, row ``K + k``
    elements ``[128 k + 64, 128 k + 192)``: twice the plain view's bytes
    but for two rows."""
    buf = np.arange(10, 10 + n, dtype=np.int32)
    ctx = codegen._Ctx(8, 0, 8, 8, {})
    ctx.bufs["t"] = jnp.asarray(buf)
    view = np.asarray(ctx.rows_view("t", overlapping=True))
    k = view.shape[0] // 2
    assert view.shape == (2 * k, 128) and k == (n + 127) // 128 + 1
    at = np.arange(k)[:, None] * 128 + np.arange(128)[None, :] - 128
    assert (view[:k] == buf[np.clip(at, 0, n - 1)]).all()
    assert (view[k:] == buf[np.clip(at + 64, 0, n - 1)]).all()
    assert ctx.rows_view("t", overlapping=True) is ctx.rows_view("t", True)
    assert np.asarray(ctx.rows_view("t")).shape == (k + 1, 128)


def _eqns(jaxpr):
    """All equations of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _count(eqns, primitive, ndim=None):
    return sum(e.primitive.name == primitive
               and (ndim is None or e.outvars[0].aval.ndim == ndim)
               for e in eqns)


def test_a_refill_is_one_gather_and_six_stages():
    """The refill's structure: ONE gather a table (PR 26 had two and a
    concatenate), one transposition, six selects between slices of whole
    lines ``[lines, 128]`` (bits 32 ... 1 of the offset)."""
    import jax

    ctx = codegen._Ctx(8, 0, 8, 8, {})
    ctx.bufs["t"] = jnp.arange(5000, dtype=jnp.float32)
    jaxpr = jax.make_jaxpr(lambda j: codegen._run_window(ctx, "t", j))(
        jnp.zeros(512, jnp.int32))
    eqns = list(_eqns(jaxpr.jaxpr))
    assert _count(eqns, "gather") == 1
    assert _count(eqns, "transpose") == 1
    stages = [e for e in eqns if e.primitive.name == "select_n"
              and e.outvars[0].aval.ndim == 2]
    assert [e.outvars[0].aval.shape for e in stages] == [
        (rows * 4, 128) for rows in (63, 47, 39, 35, 33, 32)]
    assert _count(eqns, "concatenate") == 1  # the view, outside the refill


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_the_spmv_kernel_gathers_once_a_run_table(platform):
    """HPCG's SpMV kernel as the benchmark runs it: ``col[j]`` and
    ``val[j]`` are run tables (one gather each, in the refill, six stages
    each) and ``x[col[j]]`` is the one per-pass gather; ``rowptr[i]`` and
    ``rowptr[i + 1]`` are slices."""
    import jax

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "hpcg_spmv.cl")) as f:
        src = f.read()
    n, nnz = 1024, 27 * 1024
    fn, info = KernelProgram(src).launcher("spmv", n, 256, n, platform=platform)
    assert info.lowering == "xla"
    bufs = (jnp.zeros(n + 1, jnp.int32), jnp.zeros(nnz, jnp.int32),
            jnp.zeros(nnz, jnp.float32), jnp.zeros(n, jnp.float32),
            jnp.zeros(n, jnp.float32))
    eqns = list(_eqns(jax.make_jaxpr(fn)(0, bufs, (np.float32(1.0),)).jaxpr))
    assert _count(eqns, "gather") == 3
    # and on a TPU lane the lane pick of ``x[col[j]]``'s row
    assert _count(eqns, "select_n", ndim=2) == 12 + (platform == "tpu")
