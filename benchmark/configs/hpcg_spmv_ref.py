"""hpcg_spmv: data recipe, plain reference, control, kernel cost.

The matrix is HPCG's 27-point operator on an nx x ny x nz grid
(``GenerateProblem_ref.cpp``): row ``i = (iz * ny + iy) * nx + ix``, diagonal
26, every neighbour of the 3 x 3 x 3 neighbourhood that exists -1, the
neighbours in the order of the source's ``sz, sy, sx`` loops.  ``inputs``
lays it out in CSR for the kernel.  The reference never reads those arrays:
it computes ``y = alpha * (27 x - sum of x over the neighbourhood that
exists)`` on the grid, 27 shifted adds in numpy float64, so a fault in the
CSR builder shows like a fault in the program.  It imports nothing of the
program.
"""

import numpy as np

OFFSETS = [(sz, sy, sx) for sz in (-1, 0, 1) for sy in (-1, 0, 1)
           for sx in (-1, 0, 1)]
DIAGONAL = OFFSETS.index((0, 0, 0))


def grid(cfg) -> tuple[int, int, int]:
    return int(cfg["nx"]), int(cfg["ny"]), int(cfg["nz"])


def nonzeros(cfg) -> int:
    nx, ny, nz = grid(cfg)
    return (3 * nx - 2) * (3 * ny - 2) * (3 * nz - 2)


def _stored(n: int) -> np.ndarray:
    """[n] int32: how many of the neighbours at -1, 0, +1 along one axis lie
    inside the grid (2 at the two faces, 3 between; 1 on a grid one wide)."""
    at = np.arange(n)
    return (at > 0).astype(np.int32) + 1 + (at < n - 1)


def _csr_dense(nx: int, ny: int, nz: int) -> dict:
    """The CSR arrays through a dense [n, 27] table of neighbour rows and
    the mask of those that exist (all but the grid's six faces, struck out
    face by face); the mask picks the stored columns in row-major order,
    which is CSR's.  Vectorised over the 27 offsets."""
    n = nx * ny * nz
    exists = np.ones((nz, ny, nx, 3, 3, 3), np.bool_)
    exists[0, :, :, 0], exists[-1, :, :, 2] = False, False
    exists[:, 0, :, :, 0], exists[:, -1, :, :, 2] = False, False
    exists[:, :, 0, :, :, 0], exists[:, :, -1, :, :, 2] = False, False
    shift = np.array([(sz * ny + sy) * nx + sx for sz, sy, sx in OFFSETS],
                     np.int32)
    col = (np.arange(n, dtype=np.int32)[:, None]
           + shift[None, :])[exists.reshape(n, 27)]
    cz, cy, cx = _stored(nz), _stored(ny), _stored(nx)
    rowptr = np.zeros(n + 1, np.int32)
    np.cumsum((cz[:, None, None] * cy[None, :, None]
               * cx[None, None, :]).ravel(), out=rowptr[1:])
    # the diagonal sits behind the row's stored neighbours that precede it:
    # the plane below, the line before, the row to the left
    before = ((np.arange(nz) > 0)[:, None, None] * cy[None, :, None]
              * cx[None, None, :]
              + (np.arange(ny) > 0)[None, :, None] * cx[None, None, :]
              + (np.arange(nx) > 0)[None, None, :]).ravel()
    val = np.full(col.size, -1.0, np.float32)
    val[rowptr[:-1] + before] = 26.0
    return {"rowptr": rowptr, "col": col, "val": val}


def csr(cfg) -> dict:
    """``rowptr`` [n + 1], ``col`` and ``val`` [nnz], with no loop over rows
    or planes.  Every plane of the grid between its first and its last
    stores the same pattern, shifted by a plane of rows: the arrays of a
    grid three planes thick are made through the dense table, and the middle
    plane's are laid down nz - 2 times, so that every stored nonzero is
    written once."""
    nx, ny, nz = grid(cfg)
    if nz <= 3:
        return _csr_dense(nx, ny, nz)
    thin = _csr_dense(nx, ny, 3)
    plane, mids = nx * ny, nz - 2
    at = [int(thin["rowptr"][k * plane]) for k in range(4)]
    per_mid = at[2] - at[1]
    tail = at[1] + mids * per_mid
    counts = np.diff(thin["rowptr"]).reshape(3, plane)
    rowptr = np.zeros(nx * ny * nz + 1, np.int32)
    np.cumsum(np.concatenate([counts[0], np.tile(counts[1], mids),
                              counts[2]]), out=rowptr[1:])
    col = np.empty(tail + at[3] - at[2], np.int32)
    val = np.empty(col.size, np.float32)
    col[:at[1]], val[:at[1]] = thin["col"][:at[1]], thin["val"][:at[1]]
    np.add(thin["col"][None, at[1]:at[2]],
           (np.arange(mids, dtype=np.int32) * plane)[:, None],
           out=col[at[1]:tail].reshape(mids, per_mid))
    val[at[1]:tail].reshape(mids, per_mid)[:] = thin["val"][None, at[1]:at[2]]
    col[tail:] = thin["col"][at[2]:] + np.int32((nz - 3) * plane)
    val[tail:] = thin["val"][at[2]:]
    return {"rowptr": rowptr, "col": col, "val": val}


def inputs(cfg, params, rng):
    nx, ny, nz = grid(cfg)
    n = nx * ny * nz
    if n != int(params["n"]):
        raise ValueError(f"n {params['n']} is not the configuration's "
                         f"{nx} x {ny} x {nz} grid: one work-item a row")
    arrays = csr(cfg)
    arrays["x"] = rng.standard_normal(n, dtype=np.float32)
    arrays["y"] = np.zeros(n, np.float32)
    return arrays, (float(cfg["alpha_cycle"][0]),)


def call_values(cfg, params, values):
    """alpha goes through a cycle of powers of two (exact), another in every
    call, so that ``y`` after the window is what the window's LAST call wrote;
    the last warm-up call and the fresh call take an alpha set apart."""
    return {"cycle": [(float(a),) for a in cfg["alpha_cycle"]],
            "apart": (float(cfg["alpha_apart"]),)}


def neighbourhood_sum(g: np.ndarray) -> np.ndarray:
    """Sum over the 3 x 3 x 3 neighbourhood that exists, centre included:
    27 shifted adds on the grid padded with zeros."""
    nz, ny, nx = g.shape
    p = np.pad(g, 1)
    out = np.zeros_like(g)
    for sz, sy, sx in OFFSETS:
        out += p[1 + sz:1 + sz + nz, 1 + sy:1 + sy + ny, 1 + sx:1 + sx + nx]
    return out


def product(cfg, x, alpha, precision=None) -> np.ndarray:
    """``alpha * A x`` from the grid.  float64 by default.
    ``precision="bfloat16"`` is the control: ``x`` and every product
    ``val * x`` in bfloat16 (26 x rounds, -x does not), the row's sum and
    alpha's product kept in float32 — the mildest lower-precision kernel a
    later PR could be tempted by."""
    nx, ny, nz = grid(cfg)
    if precision is None:
        g = x.astype(np.float64).reshape(nz, ny, nx)
        return (float(alpha) * (27.0 * g - neighbourhood_sum(g))).ravel()
    if precision != "bfloat16":
        raise ValueError(f"no control in precision {precision!r}")
    import ml_dtypes

    bf = ml_dtypes.bfloat16
    g = x.astype(bf).astype(np.float32).reshape(nz, ny, nx)
    centre = (np.float32(26.0) * g).astype(bf).astype(np.float32)
    return (np.float32(alpha)
            * (centre - (neighbourhood_sum(g) - g))).ravel().astype(np.float64)


def _rel_err(got, want) -> float:
    scale = float(np.abs(want).max())
    return float(np.abs(got - want).max() / scale) if scale > 0 else float("nan")


def compare(cfg, params, arrays, values, observed, seed, precision=None):
    """Every row of ``y``, twice, as max |error| over max |value|:
    ``y_window_rel_err`` is ``y`` as the window left it against the product
    with its last call's alpha, ``y_fresh_rel_err`` is ``y`` as the fresh call
    wrote it, into an array poisoned anew, with the alpha set apart."""
    from cells import Compared

    out = []
    for name, seen in (("y_window_rel_err", observed),
                       ("y_fresh_rel_err", observed["fresh"])):
        alpha = seen["values"][0]
        want = product(cfg, arrays["x"], alpha)
        got = (seen["outputs"]["y"] if precision is None
               else product(cfg, arrays["x"], alpha, precision))
        out.append(Compared(name, _rel_err(got, want),
                            cfg["limits"]["y_rel_err"]))
    return out


def kernel_cost(cfg, params, items: int) -> dict:
    """One product over ``items`` rows of the n: a multiply and an add a
    stored nonzero and alpha's multiply a row; the LEAST bytes: ``col`` and
    ``val`` once, ``rowptr`` once, each of ``x`` and ``y`` once (a launch over
    part of the rows takes its share of all of it)."""
    nx, ny, nz = grid(cfg)
    n, nnz = nx * ny * nz, nonzeros(cfg)
    share = items / n
    return {"ops": share * (2.0 * nnz + n),
            "bytes": share * (8.0 * nnz + 4.0 * (n + 1) + 4.0 * n + 4.0 * n)}
