"""Oracle-based differential testing of the full kernel language.

tests/kernel_oracle.py executes kernels one work item at a time with real
Python control flow — the language's semantic definition.  The compiled
vectorized lowering must match it on: gather loops (uniform AND per-lane
indices), private arrays, divergent branches with early returns, shifted
windows, and integer arithmetic with C division semantics.

Every case is ALSO pushed through the Pallas tile lowering
(kernel/pallas_backend.py, interpret mode) whenever the kernel is inside
its subset — since the round-4 widening that includes shifted windows and
lane-uniform gathers, so most of these now fuzz three implementations
against each other (oracle / XLA / Pallas); per-lane gathers and private
arrays still fall back and are only two-way.
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from cekirdekler_tpu.kernel import codegen, lang  # noqa: E402
from tests.kernel_oracle import Oracle  # noqa: E402

N = 128


def _run_both(src: str, arrays: dict, values: dict, atol=1e-4):
    from cekirdekler_tpu.kernel.pallas_backend import (
        PallasUnsupported,
        build_kernel_fn_pallas,
    )

    kdef = lang.parse_kernels(src)[0]
    order = [p.name for p in kdef.params if p.is_pointer]
    vals = tuple(values[p.name] for p in kdef.params if not p.is_pointer)

    fn, _ = codegen.build_kernel_fn(kdef, N, 64, N)
    jarrs = tuple(jnp.asarray(arrays[n]) for n in order)
    out_c = {n: np.asarray(a) for n, a in zip(order, fn(0, jarrs, vals))}

    oracle_arrays = {n: arrays[n].copy() for n in order}
    Oracle(kdef).run(oracle_arrays, values, N)

    for n in order:
        np.testing.assert_allclose(
            out_c[n], oracle_arrays[n], rtol=1e-4, atol=atol,
            err_msg=f"compiled vs oracle divergence in array {n!r}:\n{src}",
        )

    # a kernel that multiplies with an argument inside an index is ALSO built
    # with that argument as a key, the way a launcher builds it for a call
    # that gives a plain integer (affine accesses: slices, strided windows)
    pitches = codegen.pitch_params(kdef)
    if pitches:
        keyed = fn(0, jarrs, vals, tuple(int(vals[i]) for i in pitches))
        for n, a in zip(order, keyed):
            np.testing.assert_allclose(
                np.asarray(a), oracle_arrays[n], rtol=1e-4, atol=atol,
                err_msg=f"keyed build vs oracle divergence in {n!r}:\n{src}",
            )

    # three-way: the Pallas tile lowering, when the kernel is in-subset
    try:
        pl_fn, _ = build_kernel_fn_pallas(kdef, N, 64, N, interpret=True,
                                         force=True)
    except PallasUnsupported:
        return
    for n, a in zip(order, pl_fn(0, jarrs, vals)):
        np.testing.assert_allclose(
            np.asarray(a), oracle_arrays[n], rtol=1e-4, atol=atol,
            err_msg=f"pallas vs oracle divergence in array {n!r}:\n{src}",
        )


def test_oracle_uniform_gather_loop():
    src = """
    __kernel void k(__global float* w, __global float* x, __global float* out, int m) {
        int i = get_global_id(0);
        float acc = 0.0f;
        for (int j = 0; j < m; j++) {
            acc = acc + w[j] * x[i];
        }
        out[i] = acc;
    }"""
    rng = np.random.default_rng(0)
    _run_both(src, {
        "w": rng.standard_normal(N).astype(np.float32),
        "x": rng.standard_normal(N).astype(np.float32),
        "out": np.zeros(N, np.float32),
    }, {"m": 12})


def test_oracle_per_lane_gather_and_shifted_window():
    src = """
    __kernel void k(__global int* idx, __global float* x, __global float* out) {
        int i = get_global_id(0);
        out[i] = x[idx[i]] + x[i + 1] * 0.5f;
    }"""
    rng = np.random.default_rng(1)
    _run_both(src, {
        "idx": rng.integers(0, N, N).astype(np.int32),
        "x": rng.standard_normal(N).astype(np.float32),
        "out": np.zeros(N, np.float32),
    }, {})


def test_oracle_divergent_return_then_gather():
    """The exact shape that once miscompiled: assignment after a
    divergent early return feeding a gather index."""
    src = """
    __kernel void k(__global float* x, __global float* y) {
        int i = get_global_id(0);
        int j = 0;
        if (i % 3 == 0) {
            return;
        }
        j = 2;
        y[i] = x[j] + (float)i;
    }"""
    rng = np.random.default_rng(2)
    _run_both(src, {
        "x": rng.standard_normal(N).astype(np.float32),
        "y": np.zeros(N, np.float32),
    }, {})


def test_oracle_private_array_histogramish():
    src = """
    __kernel void k(__global int* sel, __global float* out) {
        int i = get_global_id(0);
        float slots[4];
        for (int j = 0; j < 4; j++) {
            slots[j] = (float)j;
        }
        int b = sel[i];
        slots[b] = slots[b] + 100.0f;
        float s = 0.0f;
        for (int j = 0; j < 4; j++) {
            s = s + slots[j];
        }
        out[i] = s;
    }"""
    rng = np.random.default_rng(3)
    _run_both(src, {
        "sel": (rng.integers(0, 4, N)).astype(np.int32),
        "out": np.zeros(N, np.float32),
    }, {})


def test_oracle_integer_division_semantics():
    """C truncating division/remainder with mixed signs."""
    src = """
    __kernel void k(__global int* a, __global int* b, __global int* q, __global int* r) {
        int i = get_global_id(0);
        q[i] = a[i] / b[i];
        r[i] = a[i] % b[i];
    }"""
    rng = np.random.default_rng(4)
    b = rng.integers(1, 7, N).astype(np.int32) * rng.choice([-1, 1], N).astype(np.int32)
    _run_both(src, {
        "a": rng.integers(-50, 50, N).astype(np.int32),
        "b": b,
        "q": np.zeros(N, np.int32),
        "r": np.zeros(N, np.int32),
    }, {})


def test_oracle_divergent_while_with_builtins():
    src = """
    __kernel void k(__global float* x, __global float* out) {
        int i = get_global_id(0);
        float v = fabs(x[i]);
        int steps = 0;
        while (v > 0.1f && steps < 50) {
            v = v * 0.6f + sin(v) * 0.05f;
            steps = steps + 1;
        }
        out[i] = v + (float)steps;
    }"""
    rng = np.random.default_rng(5)
    _run_both(src, {
        "x": (rng.standard_normal(N) * 3).astype(np.float32),
        "out": np.zeros(N, np.float32),
    }, {})


@pytest.mark.parametrize("seed", range(12))
def test_oracle_random_gather_kernels(seed):
    """Randomized gather/branch kernels vs the oracle."""
    rng = np.random.default_rng(seed)
    shift = int(rng.integers(-2, 3))
    mod = int(rng.integers(2, 6))
    scale = float(rng.uniform(0.25, 2.0))
    src = f"""
    __kernel void k(__global int* idx, __global float* x, __global float* out) {{
        int i = get_global_id(0);
        float acc = 0.0f;
        for (int j = 0; j < {mod}; j++) {{
            acc = acc + x[idx[i] + j] * {scale}f;
        }}
        if (i % {mod} == 0) {{
            acc = acc - x[i + {shift}];
        }}
        out[i] = acc;
    }}"""
    _run_both(src, {
        "idx": rng.integers(0, N, N).astype(np.int32),
        "x": rng.standard_normal(N).astype(np.float32),
        "out": np.zeros(N, np.float32),
    }, {})


@pytest.mark.parametrize("seed", range(12))
def test_oracle_random_group_walkers(seed, monkeypatch):
    """Randomized walks at ``local id + (the same in a group)`` in a kernel
    with a tile (ISSUE 46): the group's part from the group id, a runtime
    argument and a table at the group's id; the step as ``+=``, ``= i +`` or
    a ``for``'s; a second read a group-uniform distance on; a bound that cuts
    groups and can push the walk past either end of the buffer; odd seeds
    under a per-lane ``if``.  Three ways: the oracle, the build (whose walks
    must be ``group`` reads) and the same kernel with the form switched off
    (the gather it replaces, byte for byte)."""
    rng = np.random.default_rng(900 + seed)
    start = str(rng.choice([
        "get_group_id(0) * {k} + tid + {c}", "tid + (get_group_id(0) * {k} - c)",
        "offs[get_group_id(0)] + (int)get_local_id(0)", "c + tid"])).format(
            k=int(rng.choice([17, 64, 128])), c=int(rng.integers(-70, 70)))
    far = str(rng.choice(["get_local_size(0)", "c", "get_group_id(0) * 3", "33"]))
    grid = int(rng.choice([64, 100, 128]))
    walk = [f"int i = {start}; while (i < n) {{ BODY i += {grid}; }}",
            f"int i = {start}; while (i < n) {{ BODY i = i + {grid}; }}",
            f"for (int i = {start}; i < n; i += {grid}) {{ BODY }}"][seed % 3]
    walk = walk.replace("BODY", f"acc += x[i] - x[i + {far}] * 0.5f;")
    if seed % 2:
        walk = f"if (tid % {int(rng.integers(2, 5))} != 0) {{ {walk} }}"
    src = f"""
    __kernel void k(__global float* x, __global int* offs, __global float* out,
                    int n, int c) {{
        __local float t[64];
        int tid = get_local_id(0);
        t[tid] = 2.0f;
        float acc = 0.0f;
        {walk}
        out[get_global_id(0)] = acc * t[tid];
    }}"""
    size = int(rng.choice([N + 50, 3 * N, 40]))
    arrays = {
        "x": rng.integers(-8, 9, size).astype(np.float32),
        "offs": rng.integers(-70, size, N).astype(np.int32),
        "out": np.zeros(N, np.float32),
    }
    values = {"n": int(rng.integers(0, size + 100)), "c": int(rng.integers(-9, 40))}
    _run_both(src, arrays, values, atol=0)
    kdef = lang.parse_kernels(src)[0]
    order = [arrays[k] for k in ("x", "offs", "out")]
    vals = (values["n"], values["c"])
    fn, info = codegen.build_kernel_fn(kdef, N, 64, N)
    out = np.asarray(fn(0, tuple(map(jnp.asarray, order)), vals)[2])
    inner = int("offs[" in src)
    assert (info.access["group"], info.access["gather"]) == (2, inner), src
    monkeypatch.setattr(codegen, "_group_sites", lambda *a: {})
    ref_fn, ref_info = codegen.build_kernel_fn(kdef, N, 64, N)
    want = np.asarray(ref_fn(0, tuple(map(jnp.asarray, order)), vals)[2])
    assert ref_info.access["gather"] == 2 + inner
    assert out.tobytes() == want.tobytes(), src


@pytest.mark.parametrize("seed", range(16))
def test_oracle_random_settled_walks(seed, monkeypatch):
    """Randomized walks of whole groups of 128 whose windows may be one slice
    (ISSUE 47): the pitch, the walk's first start (on a row, off it, before
    the buffer, a runtime argument's), the step (a multiple of the pitch or
    not, up or down, a literal, the ranges' or a once-assigned local's), the
    buffer (whole pitches or not), a bound that cuts groups and can push the
    walk over either end, under a per-lane or a group's ``if``, lanes that
    break.  Four ways, byte for byte: the oracle, the build (which settles the
    windows once a loop exactly where step, pitch and buffer allow it), the
    build that checks pass by pass, the gather."""
    rng = np.random.default_rng(4700 + seed)
    G, L = 3, 128
    P = int(rng.choice([128, 256, 384]))
    k = int(rng.choice([1, 1, 2, 3]))           # the step in pitches a group
    odd = int(rng.choice([0, 0, 0, 64, 128])) if P > 128 else 0
    step = P * G * k + odd
    down = bool(rng.integers(0, 4) == 0)
    elems = P * G * int(rng.integers(2, 6)) + int(rng.choice([0, 0, 0, 70]))
    first = str(rng.choice(["0", "0", "128", "5", f"-{P}", "c"]))
    if down:
        first = f"{elems - P * G} + {first}"
    far = str(rng.choice(["128", "c", "(int)get_local_size(0)", "0"]))
    by = str(rng.choice([str(step), "stride", f"get_num_groups(0) * {P * k} + {odd}"]))
    move = f"i -= {by};" if down else f"i += {by};"
    cond = "i >= n" if down else "i < n"
    leave = ("if (tid % 5 == 1 && i > 700) { break; }"
             if rng.integers(0, 3) == 0 else "")
    body = f"{leave} acc += x[i] - x[i + {far}] * 0.5f;"
    walk = [f"int i = get_group_id(0) * {P} + tid + {first};\n"
            f"        while ({cond}) {{ {body} {move} }}",
            f"for (int i = get_group_id(0) * {P} + tid + {first}; {cond}; "
            f"{move[:-1]}) {{ {body} }}"][seed % 2]
    guard = str(rng.choice(["", "", f"tid % {int(rng.integers(2, 5))} != 0",
                            "get_group_id(0) != 1", "get_group_id(0) > 0"]))
    if guard:
        walk = f"if ({guard}) {{ {walk} }}"
    src = f"""
    __kernel void k(__global const float* x, __global float* out, int n, int c) {{
        __local float t[128];
        int tid = get_local_id(0);
        const int stride = {step};
        t[tid] = 2.0f;
        float acc = 0.0f;
        {walk}
        out[get_global_id(0)] = acc * t[tid];
    }}"""
    x = rng.integers(-8, 9, elems).astype(np.float32)
    n = int(rng.integers(0, elems + 300) if not down else rng.integers(-200, elems))
    vals = (n, int(rng.choice([0, 128, 256, 7, -128])))
    kdef = lang.parse_kernels(src)[0]
    arrays = (jnp.asarray(x), jnp.zeros(G * L, jnp.float32))
    fn, info = codegen.build_kernel_fn(kdef, G * L, L, G * L)
    out = np.asarray(fn(0, arrays, vals)[1])
    host = {"x": x.copy(), "out": np.zeros(G * L, np.float32)}
    Oracle(kdef, local_size=L).run(host, dict(zip(("n", "c"), vals)), G * L)
    np.testing.assert_array_equal(out, host["out"], err_msg=src)
    once = step % P == 0 and elems % P == 0
    assert (info.access["group"], info.access["settled"]) == (2, 2 * once), src
    for switched_off in ("_settled_walks", "_group_sites"):
        with monkeypatch.context() as mp:
            mp.setattr(codegen, switched_off, lambda *a: {})
            ref_fn, ref_info = codegen.build_kernel_fn(kdef, G * L, L, G * L)
            want = np.asarray(ref_fn(0, arrays, vals)[1])
        assert not ref_info.access["settled"]
        assert out.tobytes() == want.tobytes(), (switched_off, src)


@pytest.mark.parametrize("seed", range(16))
def test_oracle_random_common_passes(seed, monkeypatch):
    """Randomized loops that lanes leave on different passes after a number
    they make together (ISSUE 51): the walker's and the bound's types, where
    the walk starts (below 0 too), the comparison and the side the bound
    stands on, the step (a literal, the ranges', a once-assigned local's,
    two moves a pass, ``++``), a bound of one term or several; three seeds in
    four with nothing in the way, the others with what must keep the loop
    masked from its first pass (a bound by lane, a walker moved under an
    ``if``, a ``break``, a per-lane ``if`` around the loop, a run-time step,
    a walk downward).  Three ways, byte for byte: the oracle, the build
    (which counts the common passes exactly where the form allows), the
    build with the analysis switched off."""
    rng = np.random.default_rng(5100 + seed)
    wt = str(rng.choice(["int", "int", "unsigned int", "long"]))
    nt = wt if wt != "int" else str(rng.choice(["int", "int", "unsigned int"]))
    # (no walker below 0 against an ``unsigned int``: C, and both builds,
    # compare it as a large number, the oracle as the two numbers)
    c = int(rng.integers(0, 200) if "unsigned" in (wt + nt)
            else rng.integers(-300, 200))
    step, move = [("64", "i += 64;"), ("get_global_size(0)", "i += get_global_size(0);"),
                  ("stride", "i += stride;"), ("100 - 36", "i += 100; MID i -= 36;"),
                  ("1", "i++;")][int(rng.integers(0, 5))]
    bound = str(rng.choice(["n", "n", "2 * n - c", "n + get_local_size(0)"]))
    op = str(rng.choice(["<", "<", "<="]))
    cond = f"i {op} {bound}" if rng.integers(0, 3) else (
        f"{bound} {'>' if op == '<' else '>='} i")
    read = "acc += x[i - c + 1] + 1.0f;"
    body = (move.replace("MID", read) if "MID" in move else f"{read} {move}")
    loop = f"while ({cond}) {{ {body} }}"
    kept = seed % 4 == 3
    if kept:
        loop = [
            f"while (i {op} {bound} + gid % 3) {{ {body} }}",
            f"while ({cond}) {{ {read} if (gid % 2) {{ i += 64; }} else {{ i += 32; }} }}",
            f"while ({cond}) {{ if (acc > 3.0f + gid % 2) {{ break; }} {body} }}",
            f"if (gid % 3 != 1) {{ {loop} }}",
            f"while ({cond}) {{ {read} i += c + 301; }}",
            f"i += 900; while (i > {bound}) {{ {read} i -= 64; }}",
        ][int(rng.integers(0, 6))]
    src = f"""
    __kernel void k(__global const float* x, __global float* y, {nt} n, {wt} c) {{
        int gid = get_global_id(0);
        const int stride = get_global_size(0) / 2 + 8;
        {wt} i = gid + c;
        float acc = 0.0f;
        {loop}
        y[gid] = acc + 0.5f * i;
    }}"""
    np_of = {"int": np.int32, "unsigned int": np.uint32, "long": np.int64}
    n = int(rng.integers(0, 60 if step == "1" else 900))
    values = {"n": np_of[nt](n), "c": np_of[wt](c)}
    arrays = {"x": rng.integers(0, 5, 700).astype(np.float32),
              "y": np.zeros(N, np.float32)}
    kdef = lang.parse_kernels(src)[0]
    vals = (values["n"], values["c"])
    dev = (jnp.asarray(arrays["x"]), jnp.asarray(arrays["y"]))
    fn, info = codegen.build_kernel_fn(kdef, N, 64, N)
    out = np.asarray(fn(0, dev, vals)[1])
    assert info.loops_peeled == (not kept), src
    with monkeypatch.context() as mp:
        mp.setattr(codegen, "_common_walks", lambda *a: {})
        off_fn, off_info = codegen.build_kernel_fn(kdef, N, 64, N)
        want = np.asarray(off_fn(0, dev, vals)[1])
    assert off_info.loops_peeled == 0
    assert out.tobytes() == want.tobytes(), src
    Oracle(kdef).run(arrays, values, N)
    np.testing.assert_array_equal(out, arrays["y"], err_msg=src)


@pytest.mark.parametrize("seed", range(8))
def test_oracle_random_moved_value_parameters(seed, monkeypatch):
    """A value parameter moved by a group-uniform amount where the lanes of a
    group part ways (a per-lane ``if``, a loop they leave on different passes)
    is neither group-uniform nor ``local id + u``: what it held first is the
    caller's.  Every read that names it keeps the gather, in a kernel with a
    tile too; the oracle, the build and the build with the form switched off
    agree byte for byte."""
    rng = np.random.default_rng(1300 + seed)
    m, d = int(rng.integers(2, 5)), int(rng.integers(1, 9))
    far = str(rng.choice(["tid", "get_local_size(0)", "get_group_id(0) * 3", "33"]))
    move = [f"if (tid % {m} != 0) {{ c += {d}; acc += x[c] + x[c + {far}]; }}",
            f"while (c < n + tid % {m}) {{ acc += x[c] - x[c + {far}]; c += {d}; }}",
            f"for (int k = 0; k < 2 + tid % {m}; k++) {{ c = c + {d}; "
            f"acc += x[c + {far}]; }}",
            f"if (tid % {m} == 0) {{ c = tid + {d}; }} else {{ c -= {d}; }}"
            ][seed % 4]
    src = f"""
    __kernel void k(__global float* x, __global float* out, int n, int c) {{
        __local float t[64];
        int tid = get_local_id(0);
        t[tid] = 2.0f;
        float acc = 0.0f;
        {move}
        acc += x[c] + x[c + tid];
        out[get_global_id(0)] = acc * t[tid];
    }}"""
    size = int(rng.choice([N + 50, 3 * N, 40]))
    arrays = {"x": rng.integers(-8, 9, size).astype(np.float32),
              "out": np.zeros(N, np.float32)}
    values = {"n": int(rng.integers(0, size + 100)), "c": int(rng.integers(-9, 40))}
    _run_both(src, arrays, values, atol=0)
    kdef = lang.parse_kernels(src)[0]
    order = tuple(jnp.asarray(arrays[k]) for k in ("x", "out"))
    vals = (values["n"], values["c"])
    fn, info = codegen.build_kernel_fn(kdef, N, 64, N)
    out = np.asarray(fn(0, order, vals)[1])
    assert info.access["group"] == 0 and info.access["gather"] >= 2, src
    monkeypatch.setattr(codegen, "_group_sites", lambda *a: {})
    ref_fn, _ = codegen.build_kernel_fn(kdef, N, 64, N)
    assert out.tobytes() == np.asarray(ref_fn(0, order, vals)[1]).tobytes(), src


# -- random nested bodies around walkers (REVIEW 46) --------------------------
# What the group form's analysis must get right is WHERE an assignment stands:
# ifs and loops the lanes of a group walk together or apart, break / continue,
# walkers off walkers, amounts that are group-uniform, lane-varying or float.
# The build is held to the same kernel built with the form switched off.

_UNI = ["c", "5", "get_group_id(0) * 3", "n", "offs[get_group_id(0)]",
        "(int)get_local_size(0)", "(int)(f * 2.0f)"]
_LANE = ["tid % 3", "gid % 2", "offs[gid]"]
_FLOAT = ["f", "2.5f"]


def _nested_stmt(r, depth: int, walkers: list, inloop: bool, fixed=()) -> str:
    """One random statement; ``fixed``: the walkers a ``while`` around it
    steps (never assigned inside it: the loop must end)."""
    k, w = r.random(), r.choice(walkers)
    inner = lambda ws=walkers, loop=inloop, fx=fixed: " ".join(  # noqa: E731
        _nested_stmt(r, depth + 1, ws, loop, fx) for _ in range(r.randint(1, 3)))
    apart = r.choice([f"tid % {r.randint(2, 4)} == {r.randint(0, 1)}",
                      f"(tid + {r.randint(0, 3)}) % 3 == 0"])
    together = r.choice(["get_group_id(0) % 2 == 0", "c > 3", "n < 100"])
    if k < 0.25 or (k < 0.50 and w in fixed):
        return f"v += x[{w} + {r.choice(_UNI + ['0'])}];"
    if k < 0.40:
        return f"{w} {r.choice(['+=', '-='])} {r.choice(_UNI * 2 + _FLOAT + _LANE)};"
    if k < 0.45:
        return f"{w} = {r.choice(walkers + ['tid'])} + {r.choice(_UNI + _LANE)};"
    if k < 0.50:
        return f"{w}++;"
    if k < 0.53 and inloop:
        return (f"if ({r.choice([apart, together])}) "
                f"{{ {r.choice(['continue;', 'break;'])} }}")
    if depth >= 3:
        return f"v += x[{w}];"
    if k < 0.68:
        other = inner() if r.random() < 0.4 else ""
        return f"if ({apart}) {{ {inner()} }} else {{ {other} }}"
    if k < 0.76:
        return f"if ({together}) {{ {inner()} }}"
    if k < 0.86:
        kk = f"k{r.randint(0, 999)}"
        bound = r.choice(["3", "2 + tid % 3", "2 + get_group_id(0) % 2"])
        return (f"for (int {kk} = 0; {kk} < {bound}; {kk}++) "
                f"{{ {inner(loop=True)} }}")
    if k < 0.93:
        nw = f"w{r.randint(0, 999)}"
        return (f"int {nw} = {r.choice(walkers + ['tid'])} + {r.choice(_UNI)}; "
                f"while ({nw} < n + {r.choice(['0', 'tid % 2', 'get_group_id(0)'])}) "
                f"{{ {nw} += {r.choice(['64', '100', 'get_local_size(0)'])}; "
                f"{inner(walkers + [nw], True, tuple(fixed) + (nw,))} }}")
    nw = f"d{r.randint(0, 999)}"
    return (f"int {nw} = {r.choice(walkers + ['tid', 'tid'])} + "
            f"{r.choice(_UNI + _FLOAT + _LANE)}; v += x[{nw}];")


@pytest.mark.parametrize("seed", range(24))
def test_random_nested_bodies_read_what_the_gather_reads(seed, monkeypatch):
    """(The tree REVIEW 46 read fails seed 2: a ``long`` walker moved by
    ``2.5f`` under a per-lane ``if``.)"""
    import random

    r = random.Random(seed)
    body = (f"int i = tid + {r.choice(_UNI)}; "
            "long j = get_group_id(0) * 64 + tid;\n" + "\n".join(
                _nested_stmt(r, 0, ["i", "j"], False)
                for _ in range(r.randint(2, 5))))
    src = f"""
    __kernel void k(__global const float* x, __global const int* offs,
                    __global float* out, int n, int c, float f) {{
        __local float t[64];
        int tid = get_local_id(0);
        int gid = get_global_id(0);
        t[tid] = 1.0f;
        float v = 0.0f;
        {body}
        out[gid] = v + t[tid];
    }}"""
    rng = np.random.default_rng(seed)
    size = 3 * 64
    elems = int(rng.choice([400, 1000, 200]))
    x = (rng.integers(0, 1000, elems) + np.arange(elems) * 1024).astype(np.float32)
    offs = rng.integers(-80, elems + 80, size).astype(np.int32)
    arrays = (jnp.asarray(x), jnp.asarray(offs), jnp.zeros(size, jnp.float32))
    vals = (int(rng.integers(0, elems + 50)), int(rng.integers(-9, 40)),
            float(rng.choice([1.5, -3.5, 2.25])))
    kdef = lang.parse_kernels(src)[0]
    fn, _info = codegen.build_kernel_fn(kdef, size, 64, size)
    out = np.asarray(fn(0, arrays, vals)[2])
    monkeypatch.setattr(codegen, "_group_sites", lambda *a: {})
    ref_fn, ref_info = codegen.build_kernel_fn(kdef, size, 64, size)
    assert not ref_info.access.get("group")
    assert out.tobytes() == np.asarray(ref_fn(0, arrays, vals)[2]).tobytes(), src


AFFINE_FORMS = {
    # the column walk, the row walk, an array of structures' field, a
    # structure of arrays' field: ``s * gid + u`` with ``u`` lane-uniform
    "col": ("a[j * p + i + {c}]", "p"),
    "row": ("a[i * p + j + {c}]", "p"),
    "aos": ("a[{s} * i + j + {c}]", "{s}"),
    "soa": ("a[i + j * p + {c}]", "t"),
}


@pytest.mark.parametrize("seed", range(16))
def test_oracle_random_affine_kernels(seed):
    """Randomized affine index forms vs the oracle, in a counted loop and
    (odd seeds) with a divergent loop inside it; guarded and not; an offset
    that can push the walk past either end of the buffer (loads clamp); the
    lane's own element accumulated in global memory."""
    rng = np.random.default_rng(500 + seed)
    form = sorted(AFFINE_FORMS)[seed % 4]
    index, bound = AFFINE_FORMS[form]
    stride = int(rng.integers(2, 5))
    pitch = int(rng.choice([8, 24, N]))
    index = index.format(c=int(rng.integers(-3, 4)), s=stride)
    trips = int(rng.integers(1, 9))
    guard = int(rng.choice([N, N - 37]))
    body = f"x[i] += {index} * y[j];"
    if seed % 2:
        body = f"""int u = 0;
            while (u < (i & 3)) {{ {body} u++; }}"""
    src = f"""
    __kernel void k(__global float* a, __global float* x, __global float* y,
                    int p, int t, int g) {{
        int i = get_global_id(0);
        if (i < g) {{
            for (int j = 0; j < {bound.format(s=stride)}; j++) {{
                {body}
            }}
        }}
    }}"""
    size = {"aos": stride * N, "row": pitch * N}.get(form, pitch * 8 + N)
    _run_both(src, {
        "a": rng.standard_normal(size).astype(np.float32),
        "x": rng.standard_normal(N).astype(np.float32),
        "y": rng.standard_normal(N).astype(np.float32),
    }, {"p": pitch, "t": trips, "g": guard})


def test_oracle_break_in_divergent_loop():
    src = """
    __kernel void k(__global float* x, __global float* out) {
        int i = get_global_id(0);
        float acc = 0.0f;
        for (int j = 0; j < 20; j++) {
            acc = acc + x[i] * 0.1f;
            if (acc > 1.0f) {
                break;
            }
            acc = acc + 0.01f;
        }
        out[i] = acc;
    }"""
    rng = np.random.default_rng(10)
    _run_both(src, {
        "x": (rng.standard_normal(N) * 2).astype(np.float32),
        "out": np.zeros(N, np.float32),
    }, {})


def test_oracle_continue_skips_rest_but_runs_step():
    src = """
    __kernel void k(__global float* out) {
        int i = get_global_id(0);
        float s = 0.0f;
        for (int j = 0; j < 10; j++) {
            if (j % 2 == (i % 2)) {
                continue;
            }
            s = s + (float)j;
        }
        out[i] = s;
    }"""
    _run_both(src, {"out": np.zeros(N, np.float32)}, {})


def test_oracle_break_continue_mixed_while():
    src = """
    __kernel void k(__global float* x, __global float* out) {
        int i = get_global_id(0);
        float v = x[i];
        int n = 0;
        while (n < 30) {
            n = n + 1;
            if (v < 0.0f) {
                v = v + 0.5f;
                continue;
            }
            v = v * 0.8f;
            if (v < 0.05f) {
                break;
            }
        }
        out[i] = v + (float)n;
    }"""
    rng = np.random.default_rng(11)
    _run_both(src, {
        "x": (rng.standard_normal(N) * 3).astype(np.float32),
        "out": np.zeros(N, np.float32),
    }, {})


def test_oracle_break_in_do_while_first_pass():
    src = """
    __kernel void k(__global float* x, __global float* out) {
        int i = get_global_id(0);
        float v = x[i];
        int n = 0;
        do {
            if (v > 1.0f) {
                break;
            }
            v = v + 0.3f;
            n = n + 1;
        } while (n < 8);
        out[i] = v + 10.0f * (float)n;
    }"""
    rng = np.random.default_rng(12)
    _run_both(src, {
        "x": (rng.standard_normal(N) * 2).astype(np.float32),
        "out": np.zeros(N, np.float32),
    }, {})


def test_oracle_divergent_break_poisons_uniform_gather():
    """A divergent break changes per-lane trip counts: a counter in such a
    loop must NOT be treated as uniform for scalarized gathers."""
    src = """
    __kernel void k(__global float* x, __global float* w, __global float* out) {
        int i = get_global_id(0);
        int j = 0;
        float acc = 0.0f;
        while (j < 16) {
            if (x[i] * (float)j > 4.0f) {
                break;
            }
            acc = acc + w[j];
            j = j + 1;
        }
        out[i] = acc;
    }"""
    rng = np.random.default_rng(13)
    _run_both(src, {
        "x": (rng.standard_normal(N) * 2).astype(np.float32),
        "w": rng.standard_normal(N).astype(np.float32),
        "out": np.zeros(N, np.float32),
    }, {})


def test_oracle_helper_functions():
    """Non-kernel helper functions inline at call sites: scalar params,
    locals, loops inside the helper, nested helper calls."""
    src = """
    float sq(float v) {
        return v * v;
    }
    float powsum(float base, int n) {
        float acc = 0.0f;
        float p = 1.0f;
        for (int k = 0; k < n; k++) {
            p = p * base;
            acc = acc + sq(p);
        }
        return acc;
    }
    __kernel void k(__global float* x, __global float* out) {
        int i = get_global_id(0);
        out[i] = powsum(x[i] * 0.5f, 4) + sq(x[i]);
    }"""
    rng = np.random.default_rng(21)
    _run_both(src, {
        "x": rng.standard_normal(N).astype(np.float32),
        "out": np.zeros(N, np.float32),
    }, {})


def test_oracle_helper_under_divergent_branch():
    src = """
    float pick(float a, float b) {
        float r = a;
        if (b > a) {
            r = b;
        }
        return r;
    }
    __kernel void k(__global float* x, __global float* out) {
        int i = get_global_id(0);
        if (x[i] > 0.0f) {
            out[i] = pick(x[i], 2.0f);
        } else {
            out[i] = pick(-x[i], 1.0f) * 0.5f;
        }
    }"""
    rng = np.random.default_rng(22)
    _run_both(src, {
        "x": rng.standard_normal(N).astype(np.float32),
        "out": np.zeros(N, np.float32),
    }, {})


def test_oracle_helper_scoping_regressions():
    """Helpers must not see caller buffers, caller private arrays, or
    inherit kernel uniformity facts for same-named locals (review-found
    miscompilations)."""
    import pytest as _pytest

    from cekirdekler_tpu.errors import KernelCompileError, KernelLanguageError

    # same-named helper local must not inherit kernel-level uniformity
    src = """
    int tri(int idx) {
        int u = idx * (idx + 1) / 2;
        return u;
    }
    __kernel void k(__global float* x, __global float* out, int base) {
        int i = get_global_id(0);
        int u = base;
        out[i] = x[tri(i) % 8 + u];
    }"""
    rng = np.random.default_rng(31)
    _run_both(src, {
        "x": rng.standard_normal(N).astype(np.float32),
        "out": np.zeros(N, np.float32),
    }, {"base": 3})

    # helper param may shadow a caller private array's name
    src2 = """
    float pick(float w) {
        return w * 2.0f;
    }
    __kernel void k(__global float* x, __global float* out) {
        int i = get_global_id(0);
        float w[2];
        w[0] = x[i];
        out[i] = pick(w[0]);
    }"""
    _run_both(src2, {
        "x": rng.standard_normal(N).astype(np.float32),
        "out": np.zeros(N, np.float32),
    }, {})

    # buffer access inside a helper is rejected (documented contract)
    src3 = """
    float bad(float v) {
        float t = q[0];
        return v + t;
    }
    __kernel void k(__global float* q, __global float* out) {
        int i = get_global_id(0);
        out[i] = bad(q[i]);
    }"""
    from cekirdekler_tpu.kernel import codegen as _cg, lang as _lang

    kdef = _lang.parse_kernels(src3)[0]
    fn, _ = _cg.build_kernel_fn(kdef, N, 64, N)
    with _pytest.raises((KernelCompileError, KernelLanguageError)):
        fn(0, (jnp.zeros(N, jnp.float32), jnp.zeros(N, jnp.float32)), ())

    # duplicate helper definition is a parse error
    with _pytest.raises(KernelLanguageError):
        _lang.parse_kernels(
            "float f(float v){ return v; }\n"
            "float f(float v){ return v + 1.0f; }\n"
            "__kernel void k(__global float* a){}"
        )


@pytest.mark.parametrize("seed", range(8, 14))
def test_oracle_random_control_flow_kernels(seed):
    """Randomized kernels mixing helpers, break/continue, private arrays,
    and gathers — full-language oracle fuzzing."""
    rng = np.random.default_rng(100 + seed)
    trips = int(rng.integers(3, 9))
    thresh = float(rng.uniform(0.5, 3.0))
    karr = int(rng.integers(2, 5))
    src = f"""
    float fold(float a, float b) {{
        float r = a * 0.5f + b * 0.25f;
        if (r > {thresh}f) {{
            r = r - {thresh}f;
        }}
        return r;
    }}
    __kernel void k(__global int* idx, __global float* x, __global float* out) {{
        int i = get_global_id(0);
        float t[{karr}];
        for (int j = 0; j < {karr}; j++) {{
            t[j] = x[idx[i] + j] * 0.5f;
        }}
        float acc = 0.0f;
        int n = 0;
        while (n < {trips}) {{
            n = n + 1;
            float c = fold(acc, t[n % {karr}]);
            if (c < 0.0f) {{
                acc = acc + 0.25f;
                continue;
            }}
            acc = c + x[i] * 0.125f;
            if (acc > {thresh * 2}f) {{
                break;
            }}
        }}
        out[i] = acc + t[0];
    }}"""
    _run_both(src, {
        "idx": rng.integers(0, N, N).astype(np.int32),
        "x": rng.standard_normal(N).astype(np.float32),
        "out": np.zeros(N, np.float32),
    }, {})
