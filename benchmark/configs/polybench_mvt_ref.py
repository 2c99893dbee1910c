"""polybench_mvt: data recipe, plain reference, control, kernel cost.

PolyBench/GPU's MVT (``OpenCL/MVT/mvt.cl``): ``x1 += A y1`` by one kernel
and ``x2 += A^T y2`` by a second, ``A`` one dense row-major ``n x n`` float32
matrix that both kernels walk, one work-item a row (a column).  The state
``x1``, ``x2`` starts at zero and accumulates compute by compute, so after
``k`` computes it is ``k`` times the step.  The reference is numpy in
float64, computed over blocks of rows so that no float64 copy of ``A`` is
ever whole in memory.  It imports nothing of the program.
"""

import numpy as np

BLOCK_ROWS = 1024  # rows of A converted at a time (128 MB in float64 at 16384)


def size(cfg) -> int:
    return int(cfg["n_matrix"])


def inputs(cfg, params, rng):
    n = size(cfg)
    if n != int(params["n"]):
        raise ValueError(f"n {params['n']} is not the configuration's "
                         f"{n} x {n} matrix: one work-item a row")
    arrays = {"a": rng.standard_normal(n * n, dtype=np.float32),
              "x1": np.zeros(n, np.float32), "x2": np.zeros(n, np.float32),
              "y1": rng.standard_normal(n, dtype=np.float32),
              "y2": rng.standard_normal(n, dtype=np.float32)}
    return arrays, (n,)


def _bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to bfloat16 and back."""
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def step(cfg, arrays, precision=None) -> tuple[np.ndarray, np.ndarray]:
    """What ONE compute adds to the state: ``(A y1, A^T y2)``, float64.
    ``precision="bfloat16"`` is the control: ``a``, ``y1``, ``y2`` and every
    product rounded to bfloat16, the sums kept in float32 — the mildest
    lower-precision kernel a later PR could be tempted by."""
    if precision not in (None, "bfloat16"):
        raise ValueError(f"no control in precision {precision!r}")
    n = size(cfg)
    a = arrays["a"].reshape(n, n)
    low = precision is not None
    wide = np.float32 if low else np.float64
    y1, y2 = (_bf16(arrays[k]) if low else arrays[k].astype(wide)
              for k in ("y1", "y2"))
    s1, s2 = np.zeros(n, wide), np.zeros(n, wide)
    for r0 in range(0, n, BLOCK_ROWS):
        rows = slice(r0, min(n, r0 + BLOCK_ROWS))
        if low:
            block = _bf16(a[rows])
            s1[rows] = _bf16(block * y1[None, :]).sum(axis=1, dtype=wide)
            s2 += _bf16(block * y2[rows, None]).sum(axis=0, dtype=wide)
        else:
            block = a[rows].astype(wide)
            s1[rows] = block @ y1
            s2 += y2[rows] @ block
    return s1.astype(np.float64), s2.astype(np.float64)


def _rel_err(got, want) -> float:
    scale = float(np.abs(want).max())
    return float(np.abs(got - want).max() / scale) if scale > 0 else float("nan")


def window_limit(cfg, computes: int) -> float:
    """The limit of ``x_window_rel_err`` after ``computes`` computes: the
    kernel's own error plus the drift of repeated float32 additions into
    the state, which grows with the computes."""
    lim = cfg["limits"]["x_window_rel_err"]
    return float(lim["at_zero"]) + float(lim["per_compute"]) * computes


def compare(cfg, params, arrays, values, observed, seed, precision=None):
    """EVERY element of ``x1`` and ``x2``, twice, as max |error| over max
    |value| (the worse of the two vectors): ``x_window_rel_err`` is the
    state as the window left it against (computes so far) x the step,
    ``x_fresh_rel_err`` the state that the fresh call built from zero."""
    from cells import Compared

    want = step(cfg, arrays)
    ctrl = want if precision is None else step(cfg, arrays, precision)
    out = []
    for name, seen, limit in (
            ("x_window_rel_err", observed,
             window_limit(cfg, int(observed["iterations"]))),
            ("x_fresh_rel_err", observed["fresh"],
             float(cfg["limits"]["x_fresh_rel_err"]))):
        k = int(seen["iterations"])
        got = ([seen["outputs"]["x1"], seen["outputs"]["x2"]]
               if precision is None else [k * c for c in ctrl])
        out.append(Compared(
            name, max(_rel_err(g, k * w) for g, w in zip(got, want)), limit))
    return out


def kernel_cost(cfg, params, items: int) -> dict:
    """One compute (BOTH kernels) over ``items`` of the n rows: a multiply
    and an add an element of ``A`` a kernel; the LEAST bytes: ``a`` once a
    kernel, and each vector once a kernel that touches it (kernel 1 reads
    ``y1``, reads and writes ``x1``; kernel 2 the same of ``y2``, ``x2``)."""
    n = size(cfg)
    share = items / n
    return {"ops": share * 4.0 * n * n,
            "bytes": share * (2 * 4.0 * n * n + 6 * 4.0 * n)}
