"""Kernel language tests: the C-subset → vectorized JAX compiler.

Modeled on the reference's correctness matrix (Tester.cs:6763-7065 runs
{array kinds} × {dtypes} × {devices} × {pipeline} × {kernels} with
element-wise host verification); here we verify the compiler itself against
host numpy references across dtypes, operators, control flow, and builtins.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from cekirdekler_tpu.errors import KernelCompileError, KernelLanguageError
from cekirdekler_tpu.kernel import KernelProgram, extract_kernel_names, kernel, parse_kernels


def run1(src, name, arrays, values=(), n=None, local=16, chunk=None, offset=0):
    """Compile + launch one kernel over the full range; returns list of numpy arrays."""
    n = n if n is not None else len(arrays[0])
    chunk = chunk or n
    prog = KernelProgram(src)
    fn, info = prog.launcher(name, chunk, local, n)
    out = fn(offset, tuple(jnp.asarray(a) for a in arrays), tuple(values))
    return [np.asarray(o) for o in out], info


# ---------------------------------------------------------------------------
# parsing
# ---------------------------------------------------------------------------


def test_extract_kernel_names():
    src = "__kernel void foo(__global float*a){} kernel void bar(__global int*b){}"
    assert extract_kernel_names(src) == ["foo", "bar"]


def test_parse_multiple_kernels():
    ks = parse_kernels(
        "__kernel void a(__global float* x){ x[0] = 1.0f; }\n"
        "__kernel void b(__global float* x){ x[1] = 2.0f; }"
    )
    assert [k.name for k in ks] == ["a", "b"]


def test_parse_params():
    (k,) = parse_kernels(
        "__kernel void f(__global float* a, __global const int* b, float s, int n){}"
    )
    assert [p.name for p in k.params] == ["a", "b", "s", "n"]
    assert [p.is_pointer for p in k.params] == [True, True, False, False]
    assert k.params[0].ctype == "float" and k.params[3].ctype == "int"


def test_parse_errors():
    with pytest.raises(KernelCompileError):
        parse_kernels("__kernel void f(__global float* a){ a[0] = ; }")
    with pytest.raises(KernelCompileError):
        parse_kernels("void notkernel(){}")
    with pytest.raises(KernelLanguageError):
        parse_kernels("__kernel int f(__global float* a){}")
    with pytest.raises(KernelCompileError):
        parse_kernels("")


def test_unsupported_constructs():
    with pytest.raises(KernelLanguageError):
        parse_kernels("__kernel void f(__local float* s){}")
    with pytest.raises(KernelLanguageError):
        parse_kernels("#define F(x) (x)\n__kernel void f(__global float* a){}")


def test_define_substitution():
    src = """
    #define SCALE 3.0f
    #define N2 (SCALE + 1.0f)
    __kernel void f(__global float* a){
        int i = get_global_id(0);
        a[i] = a[i] * SCALE + N2;
    }"""
    (out,), _ = run1(src, "f", [np.ones(32, np.float32)])
    np.testing.assert_allclose(out, 3.0 + 4.0)


# ---------------------------------------------------------------------------
# basic compute + dtypes
# ---------------------------------------------------------------------------

DTYPES = [
    ("float", np.float32),
    ("double", np.float64),
    ("int", np.int32),
    ("uint", np.uint32),
    ("long", np.int64),
    ("uchar", np.uint8),
]


@pytest.mark.parametrize("cname,npdt", DTYPES)
def test_copy_add_matrix(cname, npdt):
    """The reference's core test pattern: c = a + b element-wise per dtype."""
    src = f"""
    __kernel void addk(__global {cname}* a, __global {cname}* b, __global {cname}* c) {{
        int i = get_global_id(0);
        c[i] = a[i] + b[i];
    }}"""
    n = 128
    a = (np.arange(n) % 17).astype(npdt)
    b = (np.arange(n) % 5).astype(npdt)
    (ra, rb, rc), info = run1(src, "addk", [a, b, np.zeros(n, npdt)])
    np.testing.assert_array_equal(rc, a + b)
    assert info.stored_params == ["c"]


def test_value_params_and_mad():
    src = """
    __kernel void saxpy(__global float* x, __global float* y, float alpha, int n) {
        int i = get_global_id(0);
        if (i < n) y[i] = mad(alpha, x[i], y[i]);
    }"""
    n = 64
    x = np.arange(n, dtype=np.float32)
    y = np.ones(n, dtype=np.float32)
    (rx, ry), _ = run1(src, "saxpy", [x, y], values=(2.5, 40))
    exp = y.copy()
    exp[:40] = 2.5 * x[:40] + 1
    np.testing.assert_allclose(ry, exp)


def test_int_division_c_semantics():
    src = """
    __kernel void divk(__global int* a, __global int* b, __global int* q, __global int* r) {
        int i = get_global_id(0);
        q[i] = a[i] / b[i];
        r[i] = a[i] % b[i];
    }"""
    a = np.array([7, -7, 7, -7, 0, 5], np.int32)
    b = np.array([2, 2, -2, -2, 3, 5], np.int32)
    (out, _, q, r), _ = run1(src, "divk", [a, b, np.zeros(6, np.int32), np.zeros(6, np.int32)], local=1)
    # C truncates toward zero
    np.testing.assert_array_equal(q, np.array([3, -3, -3, 3, 0, 1]))
    np.testing.assert_array_equal(r, np.array([1, -1, 1, -1, 0, 0]))


def test_bitwise_and_shifts():
    src = """
    __kernel void bits(__global uint* a, __global uint* out) {
        int i = get_global_id(0);
        out[i] = ((a[i] << 2) | 3u) & 255u ^ 16u;
    }"""
    a = np.arange(64, dtype=np.uint32)
    (_, out), _ = run1(src, "bits", [a, np.zeros(64, np.uint32)])
    np.testing.assert_array_equal(out, (((a << 2) | 3) & 255) ^ 16)


def test_casts():
    src = """
    __kernel void castk(__global float* a, __global int* b) {
        int i = get_global_id(0);
        b[i] = (int)(a[i] * 1.5f);
    }"""
    a = np.array([1.0, -1.0, 2.5, -2.5], np.float32)
    (_, b), _ = run1(src, "castk", [a, np.zeros(4, np.int32)], local=1)
    np.testing.assert_array_equal(b, np.array([1, -1, 3, -3]))  # trunc toward zero


def test_ternary_and_comparison():
    src = """
    __kernel void t(__global float* a, __global float* out) {
        int i = get_global_id(0);
        out[i] = a[i] > 0.0f ? a[i] : -2.0f * a[i];
    }"""
    a = np.linspace(-4, 4, 32).astype(np.float32)
    (_, out), _ = run1(src, "t", [a, np.zeros(32, np.float32)])
    np.testing.assert_allclose(out, np.where(a > 0, a, -2 * a), rtol=1e-6)


# ---------------------------------------------------------------------------
# control flow
# ---------------------------------------------------------------------------


def test_if_else_chain():
    src = """
    __kernel void f(__global int* a, __global int* out) {
        int i = get_global_id(0);
        if (a[i] < 10) { out[i] = 1; }
        else if (a[i] < 20) { out[i] = 2; }
        else { out[i] = 3; }
    }"""
    a = np.arange(30, dtype=np.int32)
    (_, out), _ = run1(src, "f", [a, np.zeros(30, np.int32)], local=1)
    np.testing.assert_array_equal(out, np.where(a < 10, 1, np.where(a < 20, 2, 3)))


def test_early_return_guard():
    src = """
    __kernel void f(__global float* a, int n) {
        int i = get_global_id(0);
        if (i >= n) return;
        a[i] = 7.0f;
    }"""
    (out,), _ = run1(src, "f", [np.zeros(64, np.float32)], values=(40,))
    assert np.all(out[:40] == 7) and np.all(out[40:] == 0)


def test_nested_if_masked_store():
    src = """
    __kernel void f(__global int* a) {
        int i = get_global_id(0);
        if (i % 2 == 0) {
            if (i % 4 == 0) { a[i] = 4; } else { a[i] = 2; }
        }
    }"""
    (out,), _ = run1(src, "f", [np.full(32, -1, np.int32)])
    exp = np.full(32, -1)
    exp[::2] = 2
    exp[::4] = 4
    np.testing.assert_array_equal(out, exp)


def test_for_loop_accumulate():
    src = """
    __kernel void f(__global float* x, __global float* out, int reps) {
        int i = get_global_id(0);
        float acc = 0.0f;
        for (int j = 0; j < reps; j++) {
            acc += x[i] * (float)j;
        }
        out[i] = acc;
    }"""
    x = np.arange(16, dtype=np.float32)
    (_, out), _ = run1(src, "f", [x, np.zeros(16, np.float32)], values=(10,))
    np.testing.assert_allclose(out, x * 45.0)


def test_data_dependent_while():
    """Collatz-ish per-item trip counts — the mandelbrot pattern."""
    src = """
    __kernel void collatz(__global int* seed, __global int* steps) {
        int i = get_global_id(0);
        int x = seed[i];
        int s = 0;
        while (x != 1 && s < 1000) {
            if (x % 2 == 0) { x = x / 2; } else { x = 3 * x + 1; }
            s++;
        }
        steps[i] = s;
    }"""
    seed = np.arange(1, 65, dtype=np.int32)

    def host(v):
        s = 0
        while v != 1 and s < 1000:
            v = v // 2 if v % 2 == 0 else 3 * v + 1
            s += 1
        return s

    (_, steps), _ = run1(src, "collatz", [seed, np.zeros(64, np.int32)])
    np.testing.assert_array_equal(steps, [host(int(v)) for v in seed])


def test_nested_loops():
    src = """
    __kernel void f(__global float* out, int n) {
        int i = get_global_id(0);
        float acc = 0.0f;
        for (int a = 0; a < n; a++) {
            for (int b = 0; b < a; b++) {
                acc += 1.0f;
            }
        }
        out[i] = acc;
    }"""
    (out,), _ = run1(src, "f", [np.zeros(8, np.float32)], values=(5,))
    np.testing.assert_allclose(out, 10.0)  # sum_{a<5} a = 10


def test_mandelbrot_exact_vs_host():
    src = """
    __kernel void mandel(__global float* out, int width, int maxIter) {
        int i = get_global_id(0);
        float cx = ((float)(i % width)) / ((float)width) * 3.0f - 2.0f;
        float cy = ((float)(i / width)) / ((float)width) * 3.0f - 1.5f;
        float zx = 0.0f; float zy = 0.0f;
        int it = 0;
        while (zx*zx + zy*zy < 4.0f && it < maxIter) {
            float t = zx*zx - zy*zy + cx;
            zy = 2.0f*zx*zy + cy;
            zx = t;
            it++;
        }
        out[i] = (float)it;
    }"""
    W, H, MAXIT = 32, 32, 40
    (out,), _ = run1(src, "mandel", [np.zeros(W * H, np.float32)], values=(W, MAXIT), local=32)

    exp = np.zeros(W * H, np.float32)
    for i in range(W * H):
        cx = (i % W) / W * 3.0 - 2.0
        cy = (i // W) / W * 3.0 - 1.5
        zx = zy = 0.0
        it = 0
        while zx * zx + zy * zy < 4.0 and it < MAXIT:
            zx, zy = np.float32(zx * zx - zy * zy + cx), np.float32(2 * zx * zy + cy)
            it += 1
        exp[i] = it
    np.testing.assert_array_equal(out, exp)


# ---------------------------------------------------------------------------
# indexing patterns
# ---------------------------------------------------------------------------


def test_stencil_shifted_reads():
    src = """
    __kernel void st(__global float* a, __global float* b) {
        int i = get_global_id(0);
        b[i] = a[i-1] + a[i] + a[i+1];
    }"""
    n = 64
    a = np.arange(n, dtype=np.float32)
    (_, b), _ = run1(src, "st", [a, np.zeros(n, np.float32)])
    exp = np.zeros(n)
    # out-of-range shifted reads CLAMP to the nearest element — the same
    # policy as the gather path (kept consistent by the oracle fuzz)
    ap = np.pad(a, 1, mode="edge")
    for i in range(n):
        exp[i] = ap[i] + ap[i + 1] + ap[i + 2]
    np.testing.assert_allclose(b, exp)


def test_chunked_launch_equals_full():
    src = """
    __kernel void st(__global float* a, __global float* b) {
        int i = get_global_id(0);
        b[i] = a[i+1] - a[i];
    }"""
    n = 128
    a = np.cumsum(np.random.RandomState(0).rand(n)).astype(np.float32)
    (_, full), _ = run1(src, "st", [a, np.zeros(n, np.float32)])
    prog = KernelProgram(src)
    fn, _ = prog.launcher("st", 32, 16, n)
    buf = jnp.zeros(n, jnp.float32)
    for off in range(0, n, 32):
        buf = fn(off, (jnp.asarray(a), buf))[1]
    np.testing.assert_allclose(np.asarray(buf), full)


def test_gather_indirect_index():
    src = """
    __kernel void g(__global int* idx, __global float* src, __global float* dst) {
        int i = get_global_id(0);
        dst[i] = src[idx[i]];
    }"""
    n = 32
    rng = np.random.RandomState(1)
    idx = rng.randint(0, n, n).astype(np.int32)
    srcv = rng.rand(n).astype(np.float32)
    (_, _, dst), _ = run1(src, "g", [idx, srcv, np.zeros(n, np.float32)])
    np.testing.assert_allclose(dst, srcv[idx])


def test_strided_access():
    src = """
    __kernel void s(__global float* a, __global float* out) {
        int i = get_global_id(0);
        out[i] = a[2*i];
    }"""
    a = np.arange(64, dtype=np.float32)
    (_, out), _ = run1(src, "s", [a, np.zeros(32, np.float32)], n=32)
    np.testing.assert_allclose(out, a[::2])


def test_elements_per_work_item_pattern():
    """Multi-element work items (reference: numberOfElementsPerWorkItem)."""
    src = """
    __kernel void two(__global float* a, __global float* b) {
        int i = get_global_id(0);
        b[2*i] = a[2*i] * 2.0f;
        b[2*i+1] = a[2*i+1] * 3.0f;
    }"""
    a = np.arange(64, dtype=np.float32)
    (_, b), _ = run1(src, "two", [a, np.zeros(64, np.float32)], n=32)
    exp = a.copy()
    exp[::2] *= 2
    exp[1::2] *= 3
    np.testing.assert_allclose(b, exp)


# ---------------------------------------------------------------------------
# builtins
# ---------------------------------------------------------------------------


def test_math_builtins():
    src = """
    __kernel void m(__global float* x, __global float* out) {
        int i = get_global_id(0);
        out[i] = sqrt(fabs(x[i])) + exp(clamp(x[i], -1.0f, 1.0f)) + fmin(x[i], 0.5f)
               + pow(fabs(x[i]) + 1.0f, 2.0f) + atan2(x[i], 2.0f);
    }"""
    x = np.linspace(-3, 3, 64).astype(np.float32)
    (_, out), _ = run1(src, "m", [x, np.zeros(64, np.float32)])
    exp = (np.sqrt(np.abs(x)) + np.exp(np.clip(x, -1, 1)) + np.minimum(x, 0.5)
           + (np.abs(x) + 1) ** 2 + np.arctan2(x, 2.0))
    np.testing.assert_allclose(out, exp, rtol=1e-5)


def test_workitem_builtins():
    src = """
    __kernel void w(__global int* gid, __global int* lid, __global int* grp, __global int* gsz) {
        int i = get_global_id(0);
        gid[i] = get_global_id(0);
        lid[i] = get_local_id(0);
        grp[i] = get_group_id(0);
        gsz[i] = get_global_size(0);
    }"""
    n, local = 64, 16
    outs, _ = run1(src, "w", [np.zeros(n, np.int32) for _ in range(4)], local=local)
    np.testing.assert_array_equal(outs[0], np.arange(n))
    np.testing.assert_array_equal(outs[1], np.arange(n) % local)
    np.testing.assert_array_equal(outs[2], np.arange(n) // local)
    np.testing.assert_array_equal(outs[3], n)


def test_select_builtin():
    src = """
    __kernel void s(__global float* a, __global float* b, __global float* out) {
        int i = get_global_id(0);
        out[i] = select(a[i], b[i], a[i] < b[i]);
    }"""
    rng = np.random.RandomState(2)
    a, b = rng.rand(32).astype(np.float32), rng.rand(32).astype(np.float32)
    (_, _, out), _ = run1(src, "s", [a, b, np.zeros(32, np.float32)])
    np.testing.assert_allclose(out, np.maximum(a, b))


def test_atomic_rejected():
    src = """
    __kernel void a(__global int* x) {
        atomic_add(x, 1);
    }"""
    prog = KernelProgram(src)
    with pytest.raises(KernelLanguageError, match="atomic"):
        fn, _ = prog.launcher("a", 8, 4, 8)
        fn(0, (jnp.zeros(8, jnp.int32),))


def test_barrier_rejected():
    """A barrier that the work items of a group do not all reach (one that
    they do is a statement like any other since ISSUE 45:
    tests/test_local_memory.py)."""
    src = """
    __kernel void b(__global float* x) {
        int i = get_global_id(0);
        if (get_local_id(0) < 3) {
            barrier(0);
        }
        x[i] = 1.0f;
    }"""
    prog = KernelProgram(src)
    with pytest.raises(KernelLanguageError, match="line 5: barrier-divergent"):
        fn, _ = prog.launcher("b", 8, 4, 8)
        fn(0, (jnp.zeros(8, jnp.float32),))


# ---------------------------------------------------------------------------
# python-kernel path
# ---------------------------------------------------------------------------


def test_python_kernel():
    @kernel
    def doubler(gid, a, factor=2.0):
        return a.at[gid].multiply(factor)

    prog = KernelProgram(doubler)
    fn, info = prog.launcher("doubler", 16, 4, 16)
    out = fn(0, (jnp.arange(16, dtype=jnp.float32),), (3.0,))
    np.testing.assert_allclose(np.asarray(out[0]), np.arange(16) * 3.0)
    assert info.array_params == ["a"] and info.value_params == ["factor"]


def test_mixed_program():
    @kernel
    def pyk(gid, a):
        return a.at[gid].add(1.0)

    src = "__kernel void ck(__global float* a){ int i = get_global_id(0); a[i] = a[i] * 2.0f; }"
    prog = KernelProgram([src, pyk])
    assert sorted(prog.kernel_names) == ["ck", "pyk"]
    f1, _ = prog.launcher("ck", 8, 4, 8)
    f2, _ = prog.launcher("pyk", 8, 4, 8)
    x = jnp.ones(8, jnp.float32)
    np.testing.assert_allclose(np.asarray(f2(0, (f1(0, (x,))[0],))[0]), 3.0)


def test_freerun_loop_var_read_in_else_branch():
    """Free-run elimination regression: a loop-carried var assigned inside
    a then-branch loop but read in the ELSE branch must stay where-merged —
    else-branch lanes keep their original value."""
    import numpy as np

    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.hardware import platforms

    src = """
    __kernel void k(__global float* c, __global float* out, __global float* xs) {
        int gid = get_global_id(0);
        float x = xs[gid];
        int i = 0;
        if (c[gid] > 0.0f) {
            while (i < 3) {
                x = x + 1.0f;
                i = i + 1;
                out[gid] = x;
            }
        } else {
            out[gid] = x;
        }
    }"""
    cr = NumberCruncher(platforms().cpus().subset(1), src)
    try:
        c = ClArray(np.array([1, -5, 2, -7] * 16, np.float32), name="c")
        xs = ClArray(np.array([1, -5, 2, -7] * 16, np.float32), name="xs")
        out = ClArray(64, np.float32, name="out")
        c.next_param(out, xs).compute(cr, 1, "k", 64, 16)
        want = np.where(
            np.array([1, -5, 2, -7] * 16) > 0,
            np.array([1, -5, 2, -7] * 16, np.float32) + 3.0,
            np.array([1, -5, 2, -7] * 16, np.float32),
        )
        np.testing.assert_allclose(np.asarray(out), want)
    finally:
        cr.dispose()


def test_freerun_inner_loop_in_do_while_body():
    """Free-run elimination regression: an inner loop inside a do-while's
    first (unconditional) body pass must NOT free-run — the body re-runs
    and reads the variable at its top."""
    import numpy as np

    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.hardware import platforms

    src = """
    __kernel void k(__global float* out) {
        int gid = get_global_id(0);
        float x = 0.0f;
        int n = 0;
        do {
            out[gid] = x;
            int i = 0;
            while (i < gid) {
                x = x + 1.0f;
                i = i + 1;
            }
            n = n + 1;
        } while (n < 2);
    }"""
    cr = NumberCruncher(platforms().cpus().subset(1), src)
    try:
        out = ClArray(4, np.float32, name="out")
        out.compute(cr, 1, "k", 4, 2)
        # second body pass records x after ONE inner-loop run: x = gid
        np.testing.assert_allclose(np.asarray(out), [0.0, 1.0, 2.0, 3.0])
    finally:
        cr.dispose()


def test_private_array_polynomial():
    """Private fixed-size arrays (``float c[4];``): constant-index stores,
    loop-variable gathers, and loop carry — evaluated against numpy."""
    import numpy as np

    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.hardware import platforms

    src = """
    __kernel void poly(__global float* x, __global float* out) {
        int i = get_global_id(0);
        float c[4];
        c[0] = 1.0f;
        c[1] = 2.0f;
        c[2] = 3.0f;
        c[3] = 4.0f;
        float acc = 0.0f;
        float p = 1.0f;
        for (int j = 0; j < 4; j++) {
            acc = acc + c[j] * p;
            p = p * x[i];
        }
        out[i] = acc;
    }"""
    cr = NumberCruncher(platforms().cpus().subset(2), src)
    try:
        xs = np.linspace(-1, 1, 256).astype(np.float32)
        x = ClArray(xs.copy(), name="x", partial_read=True)
        out = ClArray(256, np.float32, name="out")
        x.next_param(out).compute(cr, 1, "poly", 256, 64)
        want = 1 + 2 * xs + 3 * xs**2 + 4 * xs**3
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-6)
    finally:
        cr.dispose()


def test_private_array_dynamic_store_per_lane():
    """Per-lane dynamic element stores: each work item writes its own
    bucket of a private array, then reads it back."""
    import numpy as np

    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.hardware import platforms

    src = """
    __kernel void buck(__global int* sel, __global float* out) {
        int i = get_global_id(0);
        float slots[4];
        int b = sel[i];
        slots[b] = 10.0f + (float)b;
        out[i] = slots[b] + slots[0];
    }"""
    cr = NumberCruncher(platforms().cpus().subset(2), src)
    try:
        sel_np = (np.arange(128) % 4).astype(np.int32)
        sel = ClArray(sel_np.copy(), name="sel", partial_read=True)
        out = ClArray(128, np.float32, name="out")
        sel.next_param(out).compute(cr, 1, "buck", 128, 64)
        slots0 = np.where(sel_np == 0, 10.0, 0.0)
        want = (10.0 + sel_np) + slots0
        np.testing.assert_allclose(np.asarray(out), want)
    finally:
        cr.dispose()


def test_private_array_in_masked_branch():
    """Element stores under an if-mask only land for active lanes."""
    import numpy as np

    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.hardware import platforms

    src = """
    __kernel void mk(__global float* x, __global float* out) {
        int i = get_global_id(0);
        float t[2];
        t[0] = -1.0f;
        if (x[i] > 0.0f) {
            t[0] = x[i];
        }
        out[i] = t[0];
    }"""
    cr = NumberCruncher(platforms().cpus().subset(1), src)
    try:
        xs = np.array([-2.0, 3.0, -0.5, 7.0] * 16, np.float32)
        x = ClArray(xs.copy(), name="x")
        out = ClArray(64, np.float32, name="out")
        x.next_param(out).compute(cr, 1, "mk", 64, 16)
        np.testing.assert_allclose(np.asarray(out), np.where(xs > 0, xs, -1.0))
    finally:
        cr.dispose()


def test_private_array_rejected_by_pallas_subset():
    from cekirdekler_tpu.kernel import lang
    from cekirdekler_tpu.kernel.pallas_backend import (
        PallasUnsupported,
        build_kernel_fn_pallas,
    )
    import pytest as _pytest

    src = """
    __kernel void p(__global float* o) {
        int i = get_global_id(0);
        float t[2];
        t[0] = 1.0f;
        o[i] = t[0];
    }"""
    kdef = lang.parse_kernels(src)[0]
    with _pytest.raises(PallasUnsupported):
        build_kernel_fn_pallas(kdef, 256, 64, 256, interpret=True)


def test_private_array_whole_use_rejected():
    """Using a private array without an index — read or whole-assignment —
    is a language error, not silent stack corruption."""
    import numpy as np
    import pytest as _pytest

    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.errors import KernelLanguageError
    from cekirdekler_tpu.hardware import platforms

    for body in ("t = 5.0f;", "out[i] = t;"):
        src = f"""
        __kernel void k(__global float* out) {{
            int i = get_global_id(0);
            float t[2];
            t[0] = 1.0f;
            {body}
            out[i] = t[0];
        }}"""
        cr = NumberCruncher(platforms().cpus().subset(1), src)
        try:
            out = ClArray(64, np.float32, name="out")
            with _pytest.raises(KernelLanguageError):
                out.compute(cr, 1, "k", 64, 16)
            cr.reset_errors()
        finally:
            cr.dispose()


def test_private_array_loop_local_scopes_out():
    """A loop-local private array must not shadow a same-named buffer
    parameter after the loop ends."""
    import numpy as np

    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.hardware import platforms

    src = """
    __kernel void k(__global float* t, __global float* out) {
        int i = get_global_id(0);
        float acc = 0.0f;
        for (int j = 0; j < 2; j++) {
            float t[2];
            t[0] = (float)j;
            acc = acc + t[0];
        }
        out[i] = acc + t[i];
    }"""
    cr = NumberCruncher(platforms().cpus().subset(1), src)
    try:
        t = ClArray(np.full(64, 10.0, np.float32), name="t")
        out = ClArray(64, np.float32, name="out")
        t.next_param(out).compute(cr, 1, "k", 64, 16)
        np.testing.assert_allclose(np.asarray(out), 1.0 + 10.0)
    finally:
        cr.dispose()


def test_uniform_analysis_disabled_by_early_return():
    """Regression (confirmed miscompilation): a lane-divergent early
    return suppresses later assignments per-lane, so a variable assigned
    after it is NOT uniform — any `return` disables scalarized loads."""
    import numpy as np

    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.hardware import platforms

    src = """
    __kernel void k(__global float* x, __global float* y) {
        int i = get_global_id(0);
        int j = 0;
        if (i == 0) {
            return;
        }
        j = 1;
        y[i] = x[j];
    }"""
    cr = NumberCruncher(platforms().cpus().subset(1), src)
    try:
        x = ClArray(np.array([10.0, 20.0, 30.0, 40.0], np.float32), name="x")
        y = ClArray(np.zeros(4, np.float32), name="y")
        x.next_param(y).compute(cr, 1, "k", 4, 2)
        np.testing.assert_allclose(np.asarray(y), [0.0, 20.0, 20.0, 20.0])
    finally:
        cr.dispose()


def test_uniform_scalarized_gather_loop_matches():
    """The n-body pattern: a gather loop with a uniform counter must
    scalarize and still match the per-lane reference."""
    import numpy as np

    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.hardware import platforms

    src = """
    __kernel void dotrow(__global float* w, __global float* x, __global float* out,
                         int n) {
        int i = get_global_id(0);
        float acc = 0.0f;
        for (int j = 0; j < n; j++) {
            acc = acc + w[j] * x[i];
        }
        out[i] = acc;
    }"""
    cr = NumberCruncher(platforms().cpus().subset(2), src)
    try:
        rng = np.random.default_rng(3)
        # w sized to the global range (validation requires it); only the
        # first 16 entries participate in the loop
        w = ClArray(rng.standard_normal(128).astype(np.float32), name="w")
        x = ClArray(rng.standard_normal(128).astype(np.float32), name="x", partial_read=True)
        out = ClArray(128, np.float32, name="out")
        w.next_param(x, out).compute(cr, 1, "dotrow", 128, 64, values=(16,))
        want = np.float32(w.host()[:16].sum()) * x.host()
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5)
    finally:
        cr.dispose()


def test_break_outside_loop_is_parse_error():
    with pytest.raises(KernelLanguageError):
        parse_kernels("__kernel void f(__global float* a){ break; }")
    with pytest.raises(KernelLanguageError):
        parse_kernels(
            "__kernel void f(__global float* a){ if (a[0] > 0.0f) { continue; } }"
        )
