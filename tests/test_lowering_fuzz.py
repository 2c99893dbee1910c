"""Differential fuzzing of the two kernel lowerings.

Random kernels (arithmetic, builtins with safe domains, branches, bounded
loops with accumulators, statically-shifted window loads, lane-uniform
gather loops) are compiled through BOTH the vectorized XLA lowering
(kernel/codegen.py) and the Pallas tile lowering (kernel/pallas_backend.py,
interpret mode) and must agree on random inputs — any divergence is a
compiler bug in one of them.  The generator stays inside the (round-4
widened) Pallas subset so every case exercises both backends.
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from cekirdekler_tpu.kernel import codegen, lang  # noqa: E402
from cekirdekler_tpu.kernel.pallas_backend import (  # noqa: E402
    PallasUnsupported,
    build_kernel_fn_pallas,
)

N = 256


def _gen_expr(rng, depth, vars_):
    """A numerically tame float expression over the given variable names."""
    if depth <= 0 or rng.random() < 0.3:
        choices = list(vars_) + ["1.5f", "0.25f", "-2.0f", "3.0f"]
        return str(rng.choice(choices))
    kind = rng.integers(0, 5)
    a = _gen_expr(rng, depth - 1, vars_)
    b = _gen_expr(rng, depth - 1, vars_)
    if kind == 0:
        return f"({a} + {b})"
    if kind == 1:
        return f"({a} - {b})"
    if kind == 2:
        return f"({a} * {b} * 0.125f)"  # damp growth
    if kind == 3:
        return f"({a} / (1.0f + {b} * {b}))"  # denominator >= 1
    fn = rng.choice(["sin", "cos", "tanh", "sqrt", "exp"])
    if fn == "sqrt":
        return f"sqrt(fabs({a}))"
    if fn == "exp":
        return f"exp(-fabs({a}))"
    return f"{fn}({a})"


def _gen_kernel(seed: int) -> str:
    rng = np.random.default_rng(seed)
    # optionally route one subexpression through an inlined helper
    use_helper = bool(rng.integers(0, 2))
    helper = (
        "float hmix(float p, float q) {\n"
        "    float r = p * 0.5f;\n"
        "    if (q > 0.0f) {\n"
        "        r = r + q * 0.25f;\n"
        "    }\n"
        "    return r;\n"
        "}\n"
        if use_helper else ""
    )
    body = ["int i = get_global_id(0);",
            "float x = a[i];", "float y = b[i];"]
    vars_ = ["x", "y"]
    # statically-shifted window load (halo-block path): row- and/or
    # lane-crossing shifts, clamped at the buffer edge
    if rng.integers(0, 2):
        c = int(rng.choice([-257, -129, -128, -3, -1, 1, 2, 127, 128, 200]))
        body.append(f"float ws = b[i + ({c})] * 0.5f;")
        vars_.append("ws")
    # lane-uniform gather loop (SMEM operand path): streams `a` at a
    # uniform index, the n-body inner-loop shape
    if rng.integers(0, 2):
        k = int(rng.integers(3, 9))
        d = int(rng.integers(0, 4))
        body.append("float us = 0.0f;")
        body.append(
            f"for (int uj = 0; uj < {k}; uj++) "
            f"{{ us = us + a[uj + {d}] * 0.0625f; }}"
        )
        vars_.append("us")
    # a few straight-line statements
    for v in ("t0", "t1"):
        body.append(f"float {v} = {_gen_expr(rng, 3, vars_)};")
        vars_.append(v)
    if use_helper:
        body.append(f"float th = hmix({_gen_expr(rng, 2, vars_)}, y);")
        vars_.append("th")
    # a branch
    body.append(
        f"if ({_gen_expr(rng, 2, vars_)} > 0.0f) {{"
        f" t0 = {_gen_expr(rng, 2, vars_)}; }}"
        f" else {{ t1 = {_gen_expr(rng, 2, vars_)}; }}"
    )
    # a bounded loop with an accumulator (trip count varies per lane),
    # optionally with divergent break/continue
    trips = int(rng.integers(2, 6))
    exit_kind = int(rng.integers(0, 3))  # 0: none, 1: break, 2: continue
    body.append("float acc = t0;")
    body.append("int k = 0;")
    loop_body = f" acc = acc * 0.5f + {_gen_expr(rng, 2, vars_)} * 0.25f;"
    if exit_kind == 1:
        loop_body += " if (acc > 2.0f) { break; }"
    elif exit_kind == 2:
        loop_body += " k = k + 1; if (acc < 0.0f) { acc = acc + 0.125f; continue; }"
    if exit_kind != 2:
        loop_body += " k = k + 1;"
    body.append(
        f"while (k < {trips} && fabs(acc) < 50.0f) {{{loop_body} }}"
    )
    body.append("out[i] = acc + t1;")
    inner = "\n        ".join(body)
    return (
        helper
        + "__kernel void fz(__global float* a, __global float* b, "
        "__global float* out) {\n        " + inner + "\n}"
    )


@pytest.mark.parametrize("seed", range(32))
def test_lowerings_agree(seed):
    src = _gen_kernel(seed)
    kdef = lang.parse_kernels(src)[0]
    xla_fn, _ = codegen.build_kernel_fn(kdef, N, 64, N)
    try:
        pl_fn, _ = build_kernel_fn_pallas(kdef, N, 64, N, interpret=True,
                                         force=True)
    except PallasUnsupported:
        pytest.fail(f"generator left the elementwise subset:\n{src}")
    rng = np.random.default_rng(1000 + seed)
    a = jnp.asarray(rng.standard_normal(N).astype(np.float32))
    b = jnp.asarray(rng.standard_normal(N).astype(np.float32))
    out = jnp.zeros(N, jnp.float32)
    got_x = np.asarray(xla_fn(0, (a, b, out), ())[2])
    got_p = np.asarray(pl_fn(0, (a, b, out), ())[2])
    assert np.isfinite(got_x).all(), f"non-finite XLA output:\n{src}"
    np.testing.assert_allclose(
        got_p, got_x, rtol=1e-5, atol=1e-5,
        err_msg=f"lowering divergence for kernel:\n{src}",
    )


@pytest.mark.parametrize("seed", range(5))
def test_lowerings_agree_mixed_dtypes(seed):
    """The dtype-boundary contract (loads cast storage -> declared ctype,
    stores cast back) must hold for ANY caller array dtype against the
    float-declared generator kernels — output dtypes preserved, values
    within low-precision tolerance, both lowerings in agreement."""
    import jax.numpy as jnp2

    DTYPES = [jnp2.float32, jnp2.bfloat16, jnp2.float16, jnp2.int32]
    src = _gen_kernel(seed)
    kdef = lang.parse_kernels(src)[0]
    rng = np.random.default_rng(7000 + seed)
    dts = [DTYPES[rng.integers(0, len(DTYPES))] for _ in range(3)]
    arrs = tuple(
        jnp2.asarray((rng.standard_normal(N) * 2).astype(np.float32)).astype(dt)
        for dt in dts
    )
    xla_fn, _ = codegen.build_kernel_fn(kdef, N, 64, N)
    pl_fn, _ = build_kernel_fn_pallas(kdef, N, 64, N, interpret=True,
                                     force=True)
    gx = xla_fn(0, arrs, ())
    gp = pl_fn(0, arrs, ())
    for i, (a, b) in enumerate(zip(gx, gp)):
        assert a.dtype == b.dtype == arrs[i].dtype, (i, a.dtype, b.dtype)
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32), np.asarray(b, dtype=np.float32),
            rtol=3e-2, atol=3e-2,
            err_msg=f"arr{i} dtype={a.dtype} kernel:\n{src}")
