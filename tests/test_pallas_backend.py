"""Pallas tile lowering of the kernel language (kernel/pallas_backend.py):
elementwise kernels must produce bit-identical results to the vectorized
XLA lowering (codegen.py), and kernels outside the subset must be rejected
with PallasUnsupported so the registry falls back.

Runs in Pallas interpret mode on the CPU rig; the compiled-Mosaic path is
exercised on the real chip by ``chip_smoke.py`` and the benchmark's cells."""

import numpy as np
import pytest

from cekirdekler_tpu.kernel import codegen, lang
from cekirdekler_tpu.kernel.pallas_backend import (
    PallasUnsupported,
    build_kernel_fn_pallas,
)

SAXPY = """
__kernel void saxpy(__global float* x, __global float* y, float a) {
    int i = get_global_id(0);
    y[i] = a * x[i] + y[i];
}
"""

MANDEL = """
__kernel void mandel(__global float* out, float x0, float dx, int maxIter) {
    int i = get_global_id(0);
    float cx = x0 + dx * (float)i;
    float zx = 0.0f;
    float zy = 0.0f;
    int it = 0;
    while (zx*zx + zy*zy < 4.0f && it < maxIter) {
        float t = zx*zx - zy*zy + cx;
        zy = 2.0f*zx*zy + 0.1f;
        zx = t;
        it++;
    }
    out[i] = (float)it;
}
"""

MASKED = """
__kernel void maskedset(__global float* o, __global float* a) {
    int i = get_global_id(0);
    if (a[i] > 0.5f) {
        o[i] = a[i] * 2.0f;
    } else {
        o[i] = -1.0f;
    }
}
"""

GATHER = """
__kernel void gather(__global float* x, __global int* idx, __global float* o) {
    int i = get_global_id(0);
    o[i] = x[idx[i]];
}
"""

SHIFTED = """
__kernel void shift(__global float* x, __global float* o) {
    int i = get_global_id(0);
    o[i] = x[i + 1];
}
"""

STENCIL = """
__kernel void wave(__global float* p, __global float* pold, __global float* pnew) {
    int i = get_global_id(0);
    float lap = p[i-1] + p[i+1] + p[i-128] + p[i+128] + p[i-129] + p[i+129]
              + p[i-127] + p[i+127] - 8.0f*p[i];
    pnew[i] = 2.0f*p[i] - pold[i] + 0.2f*lap;
}
"""

UNIFORM_LOOP = """
__kernel void dotrow(__global float* w, __global float* x, __global float* o, int m) {
    int i = get_global_id(0);
    float acc = 0.0f;
    for (int j = 0; j < m; j++) {
        acc = acc + w[j] * x[i];
    }
    o[i] = acc + w[0];
}
"""

STORE_SHIFT_MIX = """
__kernel void m(__global float* a) {
    int i = get_global_id(0);
    a[i] = a[i + 1] * 2.0f;
}
"""


def _kdef(src: str) -> lang.KernelDef:
    return lang.parse_kernels(src)[0]


def _both(src: str, arrays, values=(), chunk=None, offset=0, global_size=None):
    """Run a kernel through the XLA lowering and the Pallas tile lowering
    (interpret mode) on identical inputs; return (xla_out, pallas_out)."""
    import jax.numpy as jnp

    kdef = _kdef(src)
    chunk = chunk or arrays[0].shape[0]
    gs = global_size or chunk
    xla_fn, _ = codegen.build_kernel_fn(kdef, chunk, 64, gs)
    pl_fn, _ = build_kernel_fn_pallas(kdef, chunk, 64, gs, interpret=True,
                                     force=True)
    jarr = tuple(jnp.asarray(a) for a in arrays)
    out_x = xla_fn(offset, jarr, values)
    out_p = pl_fn(offset, jarr, values)
    return out_x, out_p


def test_saxpy_matches_xla():
    n = 1024
    x = np.linspace(-2, 2, n).astype(np.float32)
    y = np.ones(n, np.float32)
    out_x, out_p = _both(SAXPY, (x, y), values=(3.0,))
    # 1-ulp differences allowed: the two lowerings may contract a*x+y
    # into fma differently
    np.testing.assert_allclose(np.asarray(out_x[1]), np.asarray(out_p[1]), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out_p[1]), 3.0 * x + 1.0, rtol=1e-6, atol=1e-6)


def test_while_loop_kernel_matches_xla():
    n = 512
    out = np.zeros(n, np.float32)
    out_x, out_p = _both(MANDEL, (out,), values=(-2.0, 0.004, 64))
    np.testing.assert_array_equal(np.asarray(out_x[0]), np.asarray(out_p[0]))
    got = np.asarray(out_p[0])
    assert got.min() >= 0 and got.max() <= 64 and len(np.unique(got)) > 3


def test_masked_branch_matches_xla():
    n = 256
    rng = np.random.default_rng(7)
    a = rng.random(n).astype(np.float32)
    o = np.zeros(n, np.float32)
    out_x, out_p = _both(MASKED, (o, a))
    np.testing.assert_array_equal(np.asarray(out_x[0]), np.asarray(out_p[0]))
    want = np.where(a > 0.5, a * 2.0, -1.0).astype(np.float32)
    np.testing.assert_allclose(np.asarray(out_p[0]), want, rtol=1e-6)


def test_offset_window_into_larger_buffer():
    """chunk < buffer: the Pallas path slices the window at a runtime
    offset and update-slices the result back (multi-chip range slices)."""
    n, chunk, off = 1024, 256, 384
    x = np.arange(n, dtype=np.float32)
    y = np.zeros(n, np.float32)
    out_x, out_p = _both(SAXPY, (x, y), values=(2.0,), chunk=chunk,
                         offset=off, global_size=n)
    np.testing.assert_allclose(np.asarray(out_x[1]), np.asarray(out_p[1]), rtol=1e-6, atol=1e-6)
    got = np.asarray(out_p[1])
    assert np.all(got[:off] == 0) and np.all(got[off + chunk:] == 0)
    np.testing.assert_allclose(got[off:off + chunk], 2.0 * x[off:off + chunk])


def test_per_lane_gather_rejected():
    with pytest.raises(PallasUnsupported):
        build_kernel_fn_pallas(_kdef(GATHER), 256, 64, 256, interpret=True)


def test_store_plus_shift_read_rejected():
    """A store into an array that is also shift-read would see stale
    neighbor tiles; must fall back to the XLA lowering."""
    with pytest.raises(PallasUnsupported):
        build_kernel_fn_pallas(_kdef(STORE_SHIFT_MIX), 256, 64, 256, interpret=True)


def test_shifted_window_matches_xla():
    """a[i+1] now lowers to a halo block + lane roll (widened subset)."""
    n = 1024
    x = np.arange(n, dtype=np.float32)
    o = np.zeros(n, np.float32)
    out_x, out_p = _both(SHIFTED, (x, o))
    np.testing.assert_array_equal(np.asarray(out_x[1]), np.asarray(out_p[1]))
    got = np.asarray(out_p[1])
    # edge clamp: last element reads x[n-1] (nearest valid), same as the
    # XLA padded-view semantics
    assert got[-1] == x[-1]
    np.testing.assert_array_equal(got[:-1], x[1:])


def test_stencil_multi_tap_matches_xla_across_offsets():
    """8-tap wave stencil: row- and lane-crossing shifts, offset launches
    into a larger buffer, edge-clamp agreement at both ends."""
    n, chunk = 2048, 512
    rng = np.random.default_rng(11)
    arrays = tuple(rng.standard_normal(n).astype(np.float32) for _ in range(3))
    for off in (0, 512, n - chunk):
        out_x, out_p = _both(STENCIL, arrays, chunk=chunk, offset=off,
                             global_size=n)
        np.testing.assert_allclose(
            np.asarray(out_x[2]), np.asarray(out_p[2]), rtol=1e-5, atol=1e-5)


def test_uniform_gather_loop_matches_xla():
    """The n-body shape: a lane-uniform loop index streaming a second
    buffer (SMEM operand) plus a constant-index broadcast w[0]."""
    n = 512
    rng = np.random.default_rng(13)
    w = rng.standard_normal(n).astype(np.float32)
    x = rng.standard_normal(n).astype(np.float32)
    o = np.zeros(n, np.float32)
    out_x, out_p = _both(UNIFORM_LOOP, (w, x, o), values=(17,))
    np.testing.assert_allclose(
        np.asarray(out_x[2]), np.asarray(out_p[2]), rtol=1e-5, atol=1e-5)


def test_nbody_kernel_matches_xla():
    """The full NBODY_SRC kernel (uniform x[j]/y[j]/z[j] loads + elementwise
    velocity updates) through both lowerings."""
    from cekirdekler_tpu.workloads import NBODY_SRC

    n = 256
    rng = np.random.default_rng(17)
    arrays = tuple(rng.standard_normal(n).astype(np.float32) for _ in range(6))
    kdef = {k.name: k for k in lang.parse_kernels(NBODY_SRC)}["nBody"]
    import jax.numpy as jnp

    xla_fn, _ = codegen.build_kernel_fn(kdef, n, 64, n)
    pl_fn, _ = build_kernel_fn_pallas(kdef, n, 64, n, interpret=True)
    jarr = tuple(jnp.asarray(a) for a in arrays)
    vals = (np.int32(n), np.float32(1e-3))
    out_x = xla_fn(0, jarr, vals)
    out_p = pl_fn(0, jarr, vals)
    for a, b in zip(out_x, out_p):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_smem_limit_falls_back_inside_fn(monkeypatch):
    """Uniform-read buffers beyond the SMEM budget delegate to the XLA
    lowering at trace time — same results, no failure."""
    from cekirdekler_tpu.kernel import pallas_backend

    monkeypatch.setattr(pallas_backend, "SMEM_UNIFORM_LIMIT", 64)  # bytes
    n = 512
    rng = np.random.default_rng(19)
    w = rng.standard_normal(n).astype(np.float32)
    x = rng.standard_normal(n).astype(np.float32)
    o = np.zeros(n, np.float32)
    out_x, out_p = _both(UNIFORM_LOOP, (w, x, o), values=(9,))
    np.testing.assert_allclose(
        np.asarray(out_x[2]), np.asarray(out_p[2]), rtol=1e-5, atol=1e-5)


def test_chunk_not_lane_aligned_rejected():
    with pytest.raises(PallasUnsupported):
        build_kernel_fn_pallas(_kdef(SAXPY), 200, 50, 200, interpret=True)


def test_registry_falls_back_off_tpu():
    """launcher(platform='cpu') must use the XLA path (no Mosaic on CPU);
    platform='tpu' on a gather kernel must also fall back rather than
    fail."""
    from cekirdekler_tpu.kernel.registry import KernelProgram

    prog = KernelProgram(SAXPY + GATHER)
    fn_cpu, _ = prog.launcher("saxpy", 256, 64, 256, platform="cpu")
    assert fn_cpu is not None
    fn_gather, _ = prog.launcher("gather", 256, 64, 256, platform="tpu")
    assert fn_gather is not None  # fell back to the XLA lowering


def test_shift_only_routing_veto():
    """Measured routing policy: shift-only kernels prefer the XLA lowering
    (faster on HBM-bound single-pass stencils); force=True overrides for
    direct measurement."""
    with pytest.raises(PallasUnsupported):
        build_kernel_fn_pallas(_kdef(STENCIL), 512, 64, 512, interpret=True)
    fn, _ = build_kernel_fn_pallas(_kdef(STENCIL), 512, 64, 512,
                                   interpret=True, force=True)
    assert fn is not None


def test_multi_tile_grid_halo_and_smem():
    """grid > 1 coverage for the widened paths: small block_rows force
    multiple tiles, so the pl.Element halo index map, the 8-row alignment
    rounding in _halo_rows, and per-tile SMEM loads all execute — with an
    offset launch into a larger buffer on top."""
    import jax.numpy as jnp

    MIXED = """
    __kernel void mx(__global float* w, __global float* p, __global float* o, int m) {
        int i = get_global_id(0);
        float acc = p[i-1] + p[i+1] + p[i-130] + p[i+130];
        for (int j = 0; j < m; j++) {
            acc = acc + w[j] * 0.125f;
        }
        o[i] = acc;
    }"""
    kdef = _kdef(MIXED)
    n, chunk, off = 16384, 8192, 4096
    rng = np.random.default_rng(23)
    arrays = tuple(
        jnp.asarray(rng.standard_normal(n).astype(np.float32)) for _ in range(3)
    )
    vals = (np.int32(11),)
    xla_fn, _ = codegen.build_kernel_fn(kdef, chunk, 64, n)
    # block_rows=16 -> rows=16, grid=4 (multi-tile); halo h rounds to 4
    pl_fn, _ = build_kernel_fn_pallas(kdef, chunk, 64, n, block_rows=16,
                                      interpret=True, force=True)
    for o in (0, off, n - chunk):
        got_x = xla_fn(o, arrays, vals)
        got_p = pl_fn(o, arrays, vals)
        np.testing.assert_allclose(
            np.asarray(got_x[2]), np.asarray(got_p[2]), rtol=1e-5, atol=1e-5,
            err_msg=f"grid>1 divergence at offset {o}")


def test_f16_arrays_delegate_to_xla_inside_fn():
    """float16 tiles fail the Mosaic compile on the real chip AFTER the
    registry's build-time fallback window, so the launch fn itself must
    delegate f16 arrays to the XLA lowering at trace time (probed
    on-device, r4) — including kernels whose LOOP CARRIES are seeded from
    the mismatched-dtype load (loads cast to the declared ctype; stores
    cast back to the storage dtype)."""
    n = 512
    x = np.linspace(-2, 2, n).astype(np.float16)
    y = np.ones(n, np.float16)
    out_x, out_p = _both(SAXPY, (x, y), values=(3.0,))
    np.testing.assert_allclose(np.asarray(out_x[1]), np.asarray(out_p[1]),
                               rtol=1e-2, atol=1e-2)
    # loop carry seeded from the f16 load: float-declared local must run
    # the while in f32 (declared), store back f16
    LOOPY = """
    __kernel void lp(__global float* x, __global float* o, float a) {
        int i = get_global_id(0);
        float t = x[i];
        while (t < a) {
            t = t + a * 0.25f;
        }
        o[i] = t;
    }"""
    x = (np.linspace(-2, 2, n)).astype(np.float16)
    o = np.zeros(n, np.float16)
    out_x, out_p = _both(LOOPY, (x, o), values=(1.0,))
    np.testing.assert_allclose(np.asarray(out_x[1]), np.asarray(out_p[1]),
                               rtol=1e-2, atol=1e-2)


def test_half_declared_kernel_vetoed_for_mosaic():
    """A kernel that DECLARES half (param/local/cast) creates f16 tiles
    internally regardless of the caller's array dtypes — vetoed at build
    time for compiled Mosaic, allowed in interpret mode."""
    HALFY = """
    __kernel void h(__global float* x, __global float* o) {
        int i = get_global_id(0);
        half t = (half)(x[i]);
        o[i] = (float)(t) * 2.0f;
    }"""
    with pytest.raises(PallasUnsupported):
        build_kernel_fn_pallas(_kdef(HALFY), 256, 64, 256, interpret=False,
                               force=True)
    fn, _ = build_kernel_fn_pallas(_kdef(HALFY), 256, 64, 256,
                                   interpret=True, force=True)
    assert fn is not None


def test_bf16_arrays_through_real_pallas_path():
    """bfloat16 arrays against a float-declared kernel exercise the
    actual-dtype out_shape + load/store casts on the PALLAS path (bf16 is
    not delegated — Mosaic handles it)."""
    import jax.numpy as jnp

    n = 512
    x = jnp.asarray(np.linspace(-2, 2, n), jnp.bfloat16)
    y = jnp.ones(n, jnp.bfloat16)
    kdef = _kdef(SAXPY)
    xla_fn, _ = codegen.build_kernel_fn(kdef, n, 64, n)
    pl_fn, _ = build_kernel_fn_pallas(kdef, n, 64, n, interpret=True,
                                      force=True)
    gx = xla_fn(0, (x, y), (3.0,))
    gp = pl_fn(0, (x, y), (3.0,))
    assert gp[1].dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(gx[1], dtype=np.float32), np.asarray(gp[1], dtype=np.float32),
        rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# counted loops (codegen._exec_counted): a loop every lane provably leaves on
# the same pass runs on a scalar counter, in both lowerings, against the
# scalar oracle
# ---------------------------------------------------------------------------

_LOOP_HEAD = ("__kernel void k(__global float* x, __global float* y, "
              "__global float* o, int n) {\n    int i = get_global_id(0);\n    ")

# name -> (body, (loops_counted, loops_masked))
LOOP_CASES = {
    # the passes come from the syntax: unrolled, with a remainder loop
    "for_up": ("float a = 0.0f; for (int j = 0; j < n; j++) "
               "{ a += x[j] * y[i]; } o[i] = a;", (1, 0)),
    "for_le_step3": ("float a = 0.0f; for (int j = 1; j <= n; j += 3) "
                     "{ a += x[j] * y[i]; } o[i] = a;", (1, 0)),
    "for_down_bound_left": ("float a = 0.0f; for (int j = n; 0 < j; j -= 2) "
                            "{ a += x[j] - y[i]; } o[i] = a;", (1, 0)),
    "uniform_continue": ("float a = 0.0f; for (int j = 0; j < n; j++) { "
                         "if (j % 3 == 1) continue; a += x[j] * y[i]; } "
                         "o[i] = a;", (1, 0)),
    # the condition is evaluated, on scalars, after every pass
    "while": ("float a = 0.0f; int k = 0; while (k * k < n) "
              "{ a += x[k] + y[i]; k += 2; } o[i] = a + (float)k;", (1, 0)),
    "do_while": ("float a = 0.0f; int k = 0; do { a += x[k] + y[i]; k++; } "
                 "while (k < n); o[i] = a + (float)k;", (1, 0)),
    "do_while_breaks_in_first_pass": (
        "float a = 0.0f; int k = 0; do { a += x[k] + y[i]; k++; "
        "if (k > 2) break; } while (k < n); o[i] = a + (float)k;", (1, 0)),
    "only_exit_is_a_uniform_break": (
        "float a = 0.0f; int k = 0; for (;;) { if (k >= n) break; "
        "a += x[k] * y[i]; k++; } o[i] = a + (float)k;", (1, 0)),
    "uniform_float_local_rides_as_a_scalar": (
        "float s = 0.0f; for (int j = 0; j < n; j++) { s += x[j]; } "
        "o[i] = s * y[i];", (1, 0)),
    "uniform_if_in_the_body": (
        "float a = 0.0f; int m = 0; for (int j = 0; j < n; j++) { "
        "if (j % 2 == 0) { m = m + j; a += x[m]; } else { a -= y[i]; } } "
        "o[i] = a + (float)m;", (1, 0)),
    # under a divergent `if`: the lanes outside keep `a`, the store in the
    # body stays masked, `j` is the same in every lane inside
    "under_a_divergent_if": (
        "float a = y[i]; if (y[i] > 0.0f) { for (int j = 0; j < n; j++) "
        "{ a += x[j]; o[i] = a; } } y[i] = a;", (1, 0)),
    "counted_in_a_masked_loop": (
        "float a = 0.0f; int c = (int)(fabs(y[i]) * 3.0f); int t = 0; "
        "while (t < c) { for (int j = 0; j < n; j++) { a += x[j] * 0.5f; } "
        "t++; } o[i] = a;", (1, 1)),
    "masked_in_a_counted_loop": (
        "float a = 0.0f; for (int j = 0; j < n; j++) { float z = y[i]; "
        "int t = 0; while (z < 2.0f && t < 5) { z = z * 1.5f + 0.3f; t++; } "
        "a += z * x[j]; } o[i] = a;", (1, 1)),
    "counted_in_a_counted_loop": (
        "float a = 0.0f; for (int j = 0; j < n; j++) { for (int k = 0; "
        "k < 3; k++) { a += x[j + k] * y[i]; } } o[i] = a;", (2, 0)),
    # lanes leave on different passes: the masked form, as before
    "divergent_break": ("float a = 0.0f; for (int j = 0; j < n; j++) { "
                        "a += x[i] * y[i]; if (a > 3.0f) break; } o[i] = a;",
                        (0, 1)),
    "condition_reads_a_buffer_the_loop_stores_to": (
        "int k = 0; while (o[0] < 1.0f && k < n) { o[i] = 0.5f; k++; } "
        "y[i] = (float)k;", (0, 1)),
}


def _nbody_case():
    from cekirdekler_tpu.workloads import NBODY_SRC

    return {k.name: k for k in lang.parse_kernels(NBODY_SRC)}["nBody"]


@pytest.mark.parametrize("lowering", ["xla", "pallas"])
@pytest.mark.parametrize("case", sorted(LOOP_CASES) + ["nbody"])
def test_counted_loop_matches_the_oracle(case, lowering):
    """Each loop form through one lowering against the scalar oracle, at
    trip counts 0, 1, one that the unroll does not divide and one it does;
    the build says how it lowered each loop."""
    import jax
    import jax.numpy as jnp

    from tests.kernel_oracle import Oracle

    if case == "nbody":
        kdef, want = _nbody_case(), (1, 0)
    else:
        body, want = LOOP_CASES[case]
        kdef = _kdef(_LOOP_HEAD + body + "\n}\n")
    N = 256
    names = [p.name for p in kdef.params if p.is_pointer]
    if lowering == "pallas":
        if case == "condition_reads_a_buffer_the_loop_stores_to":
            with pytest.raises(PallasUnsupported):  # stored AND uniform-read
                build_kernel_fn_pallas(kdef, N, 64, N, interpret=True)
            return
        fn, info = build_kernel_fn_pallas(kdef, N, 64, N, interpret=True,
                                          force=True)
    else:
        fn, info = codegen.build_kernel_fn(kdef, N, 64, N)
    assert (info.loops_counted, info.loops_masked) == want
    assert codegen._UNROLL > 1 and codegen._UNROLL % 2 == 0
    run = jax.jit(fn)
    rng = np.random.default_rng(29)
    for n in (0, 1, codegen._UNROLL + 3, 2 * codegen._UNROLL):
        arrays = {k: rng.standard_normal(N).astype(np.float32) for k in names}
        values = {"n": np.int32(n)}
        if case == "nbody":
            values["dt"] = np.float32(1e-3)
        if case == "condition_reads_a_buffer_the_loop_stores_to":
            arrays["o"][:] = 0.0  # every item sees o[0] < 1 until it stores
        want_arrays = {k: v.copy() for k, v in arrays.items()}
        Oracle(kdef).run(want_arrays, values, N)
        got = run(0, tuple(jnp.asarray(arrays[k]) for k in names),
                  tuple(values.values()))
        for k, g in zip(names, got):
            np.testing.assert_allclose(
                np.asarray(g), want_arrays[k], rtol=1e-4, atol=1e-4,
                err_msg=f"{case} ({lowering}), n={n}, array {k!r}")


@pytest.mark.parametrize("case", ["for_up", "under_a_divergent_if", "nbody"])
def test_counted_and_masked_forms_agree_to_the_last_bit(case):
    """Bit-identity without a switch: the condition written ``j < n + (i -
    i)`` defeats the proof, so the same kernel builds masked (and gathers
    ``x[j]`` per lane: XLA lowering only).  Same operations in the same
    order: the two builds agree exactly."""
    import jax
    import jax.numpy as jnp

    if case == "nbody":
        from cekirdekler_tpu.workloads import NBODY_SRC as src

        name, values = "nBody", (np.int32(203), np.float32(1e-3))
    else:
        src = _LOOP_HEAD + LOOP_CASES[case][0] + "\n}\n"
        name, values = "k", (np.int32(203),)
    assert "j < n;" in src
    defeated = src.replace("j < n;", "j < n + (i - i);")
    N = 256
    outs = []
    for text, want in ((src, (1, 0)), (defeated, (0, 1))):
        kdef = {k.name: k for k in lang.parse_kernels(text)}[name]
        fn, info = codegen.build_kernel_fn(kdef, N, 64, N)
        assert (info.loops_counted, info.loops_masked) == want
        rng = np.random.default_rng(31)
        arrays = tuple(jnp.asarray(rng.standard_normal(N).astype(np.float32))
                       for p in kdef.params if p.is_pointer)
        outs.append(jax.jit(fn)(0, arrays, values))
    for a, b in zip(*outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_return_switches_the_proof_off_and_spans_name_the_loops():
    """A kernel with a ``return`` builds every loop masked; ``lowering_meta``
    carries the counts onto the launch / fused / compile spans."""
    from cekirdekler_tpu.kernel.registry import KernelProgram, lowering_meta

    body, _ = LOOP_CASES["for_up"]
    src = _LOOP_HEAD + body + "\n}\n"
    returning = src.replace("float a = 0.0f;",
                            "if (i < 0) { return; } float a = 0.0f;")
    for text, loops in ((src, "counted:1;masked:0"),
                        (returning, "counted:0;masked:1")):
        _fn, info = KernelProgram(text).launcher("k", 256, 64, 256,
                                                 platform="cpu")
        assert lowering_meta((info,)) == {"lowering": "xla", "loops": loops,
                                          "views": "kept:0;built:0"}
    _fn, info = KernelProgram(MANDEL).launcher("mandel", 256, 64, 256,
                                               platform="cpu")
    assert lowering_meta((info,))["loops"] == "counted:0;masked:1"


# -- a counted loop's tile is fitted to the register file (PR 36) -----------

# (a sum of ``w[j]`` alone would be the same in every lane: no tile at all)
LIVE_1 = """
__kernel void live1(__global float* w, __global float* o, int n) {
    float acc = 0.0f;
    for (int j = 0; j < n; j++) {
        acc += w[j] * (float)get_global_id(0);
    }
    o[get_global_id(0)] = acc;
}
"""

LIVE_3 = """
__kernel void live3(__global float* x, __global float* y, __global float* o, int n) {
    int i = get_global_id(0);
    float xi = x[i];
    float yi = y[i];
    float acc = 0.0f;
    for (int j = 0; j < n; j++) {
        float dx = x[j] - xi;
        float dy = y[j] - yi;
        acc += 1.0f / (dx*dx + dy*dy + 0.0001f);
    }
    o[i] = acc;
}
"""

# four positions read a pass and eight sums carried; ``dt`` lives outside the
# loop and is not read in it, ``n`` and ``j`` are the same in every lane
LIVE_12 = """
__kernel void live12(__global float* x, __global float* y, __global float* z,
                     __global float* w, __global float* o0, __global float* o1,
                     __global float* o2, __global float* o3, int n, float dt) {
    int i = get_global_id(0);
    float xi = x[i];
    float yi = y[i];
    float zi = z[i];
    float wi = w[i];
    float scale = dt * xi;
    float a0 = 0.0f; float a1 = 0.0f; float a2 = 0.0f; float a3 = 0.0f;
    float a4 = 0.0f; float a5 = 0.0f; float a6 = 0.0f; float a7 = 0.0f;
    for (int j = 0; j < n; j++) {
        float d0 = x[j] - xi;
        float d1 = y[j] - yi;
        float d2 = z[j] - zi;
        float d3 = w[j] - wi;
        float r2 = d0*d0 + d1*d1 + d2*d2 + d3*d3 + 0.0001f;
        float inv = 1.0f / (r2 * sqrt(r2));
        a0 += d0 * inv; a1 += d1 * inv; a2 += d2 * inv; a3 += d3 * inv;
        a4 += inv; a5 += r2; a6 += d0 * d1; a7 += d2 * d3;
    }
    o0[i] = (a0 + a4) * scale; o1[i] = a1 + a5; o2[i] = a2 + a6; o3[i] = a3 + a7;
}
"""


# the loop stores to ``o``'s tile and reads ``x``'s at the lane's own index;
# ``i`` is an index and no tile; ``s`` is carried
LIVE_BUFS = """
__kernel void livebufs(__global float* w, __global float* x, __global float* o, int n) {
    int i = get_global_id(0);
    float s = 0.0f;
    for (int j = 0; j < n; j++) {
        s += w[j] * x[i];
        o[i] = o[i] + s;
    }
}
"""


def _nbody_src():
    from cekirdekler_tpu.workloads import NBODY_SRC

    return NBODY_SRC


# kernel -> (source, name, tiles its hungriest counted loop keeps alive, or
# None where the rows are not fitted: a masked loop, no loop)
TILE_KERNELS = {
    "live1": (lambda: LIVE_1, "live1", 1),
    "live3": (lambda: LIVE_3, "live3", 3),
    "live3_bufs": (lambda: LIVE_BUFS, "livebufs", 3),
    "nbody": (_nbody_src, "nBody", 6),
    "live12": (lambda: LIVE_12, "live12", 12),
    "masked_while": (lambda: MANDEL, "mandel", None),
    "no_loop": (lambda: SAXPY, "saxpy", None),
}
# rows by (live, chunk): the largest power-of-two multiple of 8 that divides
# chunk / 128, is at most 256, and holds ``live`` tiles in 48 registers
TILE_ROWS = {
    (1, 8192): 64, (1, 32768): 256, (1, 98304): 256,
    (3, 8192): 64, (3, 32768): 128, (3, 98304): 128,
    (6, 8192): 64, (6, 32768): 64, (6, 98304): 64,
    (12, 8192): 32, (12, 32768): 32, (12, 98304): 32,
}


@pytest.mark.parametrize("chunk", [8192, 32768, 98304])
@pytest.mark.parametrize("kernel", sorted(TILE_KERNELS))
def test_a_counted_loops_tile_is_fitted_to_the_register_file(kernel, chunk):
    """A kernel whose loops are all counted gets the rows at which the tiles
    its loop keeps alive fit the budget; a masked loop and a kernel without
    one keep ``min(256, chunk / 128)``.  ``lowering_meta`` names the tile."""
    from cekirdekler_tpu.kernel import pallas_backend
    from cekirdekler_tpu.kernel.registry import lowering_meta

    src, name, live = TILE_KERNELS[kernel]
    kdef = {k.name: k for k in lang.parse_kernels(src())}[name]
    _fn, info = build_kernel_fn_pallas(kdef, chunk, 256, chunk, interpret=True)
    rows_total = chunk // 128
    if live is None:
        assert info.tile_rows == min(256, rows_total)
        assert (info.loops_counted == 0) or info.loops_masked
    else:
        assert (info.loops_counted, info.loops_masked) == (1, 0)
        assert info.loop_live == live
        assert info.tile_rows == TILE_ROWS[live, chunk]
        assert info.tile_rows // 8 * live <= pallas_backend.LOOP_LIVE_VREGS
        # the next power of two up would overflow, leave the chunk
        # undivided or pass 256
        up = 2 * info.tile_rows
        assert (up // 8 * live > pallas_backend.LOOP_LIVE_VREGS
                or rows_total % up or up > 256)
    assert info.tile_rows % 8 == 0 and info.tile_rows <= 256
    assert rows_total % info.tile_rows == 0
    assert info.tile_grid == rows_total // info.tile_rows
    assert lowering_meta((info,))["tile"] == (
        f"{info.tile_rows}x128;grid={info.tile_grid};live={info.loop_live}")
    # rows given by hand are a cap and nothing is fitted
    _fn, by_hand = build_kernel_fn_pallas(kdef, chunk, 256, chunk,
                                          block_rows=256, interpret=True)
    assert by_hand.tile_rows == min(256, rows_total)


@pytest.mark.parametrize("rows_total,live,want", [
    (96, 6, 32),     # 96 rows are 72 registers of six tiles: 32 divides
    (24, 6, 24),     # fits as it stands: not made a power of two
    (12, 12, 12),    # not a multiple of 8: nothing to fit to
    (256, 100, 8),   # nothing fits: the smallest tile
    (300, 1, 4),     # the cap halved until it divides, as ever
])
def test_fitted_rows_off_the_powers_of_two(rows_total, live, want):
    from cekirdekler_tpu.kernel.pallas_backend import DEFAULT_ROWS, _fit_rows

    assert _fit_rows(rows_total, DEFAULT_ROWS, live) == want


def test_nbody_at_fitted_rows_equals_one_tall_tile_to_the_last_bit():
    """Two grid steps of 64 rows against one step of 128: each step
    runs the whole loop for its rows, so no lane's operations change order.
    The loop's passes are the ``n`` ARGUMENT's, kept short here."""
    import jax
    import jax.numpy as jnp
    from cekirdekler_tpu.kernel.registry import lowering_meta

    n = 16384
    kdef = {k.name: k for k in lang.parse_kernels(_nbody_src())}["nBody"]
    rng = np.random.default_rng(36)
    arrays = tuple(jnp.asarray(rng.standard_normal(n).astype(np.float32))
                   for _ in range(6))
    values = (np.int32(77), np.float32(1e-3))
    outs = []
    for block_rows, tile in ((None, "64x128;grid=2;live=6"),
                             (256, "128x128;grid=1;live=6")):
        fn, info = build_kernel_fn_pallas(kdef, n, 256, n, interpret=True,
                                          block_rows=block_rows)
        outs.append(jax.jit(fn)(0, arrays, values))
        assert lowering_meta((info,))["tile"] == tile
        assert info.lowering == "pallas"
    for a, b in zip(*outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(outs[0][3]).max()) > 0.0
