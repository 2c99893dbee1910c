"""wave_membrane: data recipe, plain reference, control, kernel cost.

Upstream Cekirdekler's mesh demo (``Kamera.cs:190-268``) as the port ships
it (``examples/wave_equation.py``): a 2-D membrane stepped by the leapfrog
scheme ``frame = 2 u1 - u0 + c2 lap(u1)`` with the edge held at zero
(``waveStep``), then ``u0 = u1; u1 = frame`` (``rotate``), one work-item a
cell.  The scheme dissipates nothing: a random field stays as rough as it
started, so a lane that read a neighbour's row one step late is wrong by the
size of the field; and it conserves the discrete energy

    E = ||u1 - u0||^2 + c2 <grad u1, grad u0>

(forward differences over every pair of neighbouring cells, the edge cells
zero) exactly, which checks the state a window of thousands of steps left
without replaying it.  HOW MANY steps it was advanced by, the energy cannot
say; the membrane's own modes can: the product of two sines that vanish on
the edge is an eigenvector of the laplacian, so the state's component along
it obeys a scalar recurrence with a closed form in the number of steps.  The
reference is numpy in float64; it imports nothing of the program.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

ENERGY_ROWS = 256  # rows a block of the energy sum (32 MB in float64 at 16384)


def grid(cfg) -> tuple[int, int]:
    return int(cfg["height"]), int(cfg["width"])


def inputs(cfg, params, rng):
    h, w = grid(cfg)
    if h * w != int(params["n"]):
        raise ValueError(f"n {params['n']} is not the configuration's "
                         f"{w} x {h} membrane: one work-item a cell")
    u = rng.standard_normal((h, w), dtype=np.float32)
    u[0, :] = u[-1, :] = 0.0
    u[:, 0] = u[:, -1] = 0.0
    u = u.reshape(-1)
    # zero initial velocity: u(t - dt) = u(t)
    arrays = {"u0": u.copy(), "u1": u, "frame": np.zeros(h * w, np.float32)}
    return arrays, (w, h, float(cfg["c2"]))


def _bf16(x: np.ndarray) -> np.ndarray:
    """Rounded to bfloat16 and back, in float64."""
    import ml_dtypes

    return x.astype(np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)


def replay(a, b, steps: int, c2: float, on_edge, precision=None):
    """``steps`` steps of the scheme on a patch: ``a``, ``b`` the fields at
    t - dt and t (float64, 2-D), ``on_edge`` the patch's cells that lie on
    the membrane's edge.  The patch's own border is held at zero: right
    where it is the membrane's edge, and where it is not, wrong by one more
    cell inwards a step: the caller leaves a margin of ``steps`` cells.
    Returns (u0, u1) after the steps; ``frame`` equals u1.
    ``precision="bfloat16"`` is the control: the state and the constant
    rounded to bfloat16 at every step."""
    if precision not in (None, "bfloat16"):
        raise ValueError(f"no control in precision {precision!r}")
    low = precision is not None
    if low:
        a, b, c2 = _bf16(a), _bf16(b), float(_bf16(np.float64(c2)))
    for _ in range(steps):
        c = np.zeros_like(b)
        mid = b[1:-1, 1:-1]
        lap = (b[1:-1, :-2] + b[1:-1, 2:] + b[:-2, 1:-1] + b[2:, 1:-1]
               - 4.0 * mid)
        c[1:-1, 1:-1] = 2.0 * mid - a[1:-1, 1:-1] + c2 * lap
        c[on_edge] = 0.0
        a, b = b, (_bf16(c) if low else c)
    return a, b


def whole_replay(cfg, u0, u1, steps: int, precision=None):
    """The whole membrane, for the checks at small sizes."""
    h, w = grid(cfg)
    edge = np.zeros((h, w), bool)
    edge[0, :] = edge[-1, :] = edge[:, 0] = edge[:, -1] = True
    a, b = replay(u0.reshape(h, w).astype(np.float64),
                  u1.reshape(h, w).astype(np.float64), steps,
                  float(cfg["c2"]), edge, precision)
    return a.reshape(-1), b.reshape(-1)


def tile_replay(cfg, u0, u1, steps: int, top: int, left: int, size: int,
                precision=None):
    """The ``size x size`` tile at (top, left) after ``steps`` steps, from
    the patch ``steps`` cells wider on every side (the scheme's domain of
    dependence), cut off at the membrane's edge.  Returns (u0, u1) of the
    tile, float64."""
    h, w = grid(cfg)
    r0, r1 = max(0, top - steps), min(h, top + size + steps)
    c0, c1 = max(0, left - steps), min(w, left + size + steps)
    rows = np.arange(r0, r1)[:, None]
    cols = np.arange(c0, c1)[None, :]
    edge = (rows == 0) | (rows == h - 1) | (cols == 0) | (cols == w - 1)
    a, b = replay(u0.reshape(h, w)[r0:r1, c0:c1].astype(np.float64),
                  u1.reshape(h, w)[r0:r1, c0:c1].astype(np.float64),
                  steps, float(cfg["c2"]), edge, precision)
    cut = (slice(top - r0, top - r0 + size), slice(left - c0, left - c0 + size))
    return a[cut], b[cut]


def tiles(cfg, ranges_log, seed: int) -> list[tuple[int, int]]:
    """Where the fresh call is compared, ``(top, left)`` of ``tile x tile``
    cells each: three tiles stacked over every lane boundary of the ranges
    the window ended on (the fresh call starts from them and the balancer
    may move them by a few rows), at ``tile_columns`` seeded columns each;
    ``edge_tiles`` on the membrane's edge, its corners among them;
    ``interior_tiles`` seeded anywhere."""
    h, w = grid(cfg)
    size = int(cfg["tile"])
    rng = np.random.default_rng(int(seed) ^ 0x5EED)
    top_max, left_max = h - size, w - size

    def lefts(k):
        return [int(x) for x in rng.integers(0, left_max + 1, k)]

    out = [(0, 0), (0, left_max), (top_max, 0), (top_max, left_max)]
    for k in range(max(0, int(cfg["edge_tiles"]) - 4)):
        along = int(rng.integers(0, (top_max if k % 2 else left_max) + 1))
        far = int(rng.integers(0, 2))
        out.append((along, far * left_max) if k % 2 else (far * top_max, along))
    ranges = ranges_log[-1] if ranges_log else []
    at = 0
    for share in ranges[:-1]:
        at += int(share)
        row = at // w
        for left in lefts(int(cfg["tile_columns"])):
            out += [(min(max(0, row - size // 2 + k * size), top_max), left)
                    for k in (-1, 0, 1)]
    out += [(int(rng.integers(0, top_max + 1)), left)
            for left in lefts(int(cfg["interior_tiles"]))]
    return out


def _rel_err(got, want) -> tuple[float, float]:
    return float(np.abs(got - want).max()), float(np.abs(want).max())


def fresh_rel_err(cfg, start, fresh, ranges_log, seed, precision=None):
    """max |error| over max |value| of ``u0``, ``u1`` and ``frame`` over the
    tiles, after the fresh call's steps from the state ``start``."""
    h, w = grid(cfg)
    size, steps = int(cfg["tile"]), int(fresh["iterations"])
    worst = scale = 0.0
    for top, left in tiles(cfg, ranges_log, seed):
        want = tile_replay(cfg, start["u0"], start["u1"], steps, top, left,
                           size)
        if precision is None:
            cut = (slice(top, top + size), slice(left, left + size))
            got = {k: fresh["outputs"][k].reshape(h, w)[cut]
                   for k in ("u0", "u1", "frame")}
        else:
            c0, c1 = tile_replay(cfg, start["u0"], start["u1"], steps, top,
                                 left, size, precision)
            got = {"u0": c0, "u1": c1, "frame": c1}
        for name, ref in (("u0", want[0]), ("u1", want[1]),
                          ("frame", want[1])):
            err, top_value = _rel_err(got[name], ref)
            worst, scale = max(worst, err), max(scale, top_value)
    return worst / scale if scale > 0 else float("nan")


def modes(cfg, seed: int) -> list[tuple[int, int]]:
    """``modes`` seeded mode numbers ``(a, b)`` between an eighth and a
    quarter of the grid: a phase of about half a radian a step, so that one
    step more or fewer shows."""
    h, w = grid(cfg)
    rng = np.random.default_rng(int(seed) ^ 0x30DE5)
    return [(int(rng.integers(h // 8, h // 4 + 1)),
             int(rng.integers(w // 8, w // 4 + 1)))
            for _ in range(int(cfg["modes"]))]


def sums(cfg, u0, u1, picked) -> tuple[float, np.ndarray, np.ndarray]:
    """One pass over the membrane in float64, by blocks of rows on a few
    threads (numpy lets go of the lock): the energy ``||u1 - u0||^2 + c2
    <grad u1, grad u0>``, and the components of ``u0`` and of ``u1`` along
    the modes ``sin(pi a y / (h - 1)) sin(pi b x / (w - 1))``."""
    h, w = grid(cfg)
    a2, b2 = u0.reshape(h, w), u1.reshape(h, w)
    c2 = float(cfg["c2"])
    sy = np.stack([np.sin(np.pi * a * np.arange(h) / (h - 1))
                   for a, _b in picked], axis=1)          # [h, modes]
    sx = np.stack([np.sin(np.pi * b * np.arange(w) / (w - 1))
                   for _a, b in picked], axis=1)          # [w, modes]

    def block(r0: int):
        r1 = min(h, r0 + ENERGY_ROWS)
        a = a2[r0:min(h, r1 + 1)].astype(np.float64)
        b = b2[r0:min(h, r1 + 1)].astype(np.float64)
        n = r1 - r0
        d = b[:n] - a[:n]
        total = float(np.vdot(d, d))
        total += c2 * float(np.vdot(a[:n, 1:] - a[:n, :-1],
                                    b[:n, 1:] - b[:n, :-1]))
        # pairs (r, r + 1) for r in the block; the last row has none
        m = a.shape[0] - 1
        total += c2 * float(np.vdot(a[1:m + 1] - a[:m], b[1:m + 1] - b[:m]))
        return (total, np.einsum("ym,ym->m", a[:n] @ sx, sy[r0:r1]),
                np.einsum("ym,ym->m", b[:n] @ sx, sy[r0:r1]))

    with ThreadPoolExecutor(max_workers=8) as pool:
        parts = list(pool.map(block, range(0, h, ENERGY_ROWS)))
    return (float(sum(p[0] for p in parts)), sum(p[1] for p in parts),
            sum(p[2] for p in parts))


def mode_after(cfg, picked, c_start: np.ndarray, steps: int):
    """The components of ``(u0, u1)`` along the modes after ``steps`` steps
    from ``u0 = u1`` with components ``c_start``: ``c(k + 1) = (2 - c2
    lam) c(k) - c(k - 1)``, ``c(0) = c(-1)``, in closed form."""
    h, w = grid(cfg)
    lam = np.array([4.0 * np.sin(np.pi * a / (2 * (h - 1))) ** 2
                    + 4.0 * np.sin(np.pi * b / (2 * (w - 1))) ** 2
                    for a, b in picked])
    theta = np.arccos(1.0 - 0.5 * float(cfg["c2"]) * lam)
    swing = c_start * (np.cos(theta) - 1.0) / np.sin(theta)

    def at(k):
        return c_start * np.cos(k * theta) + swing * np.sin(k * theta)

    return at(steps - 1), at(steps), np.hypot(c_start, swing)


def drift_limit(cfg, name: str, steps: int) -> float:
    """The limit of a number that drifts with a float32 state's roundings:
    what it reads at once plus so much a step."""
    lim = cfg["limits"][name]
    return float(lim["at_zero"]) + float(lim["per_step"]) * steps


def compare(cfg, params, arrays, values, observed, seed, precision=None):
    """``u_fresh_rel_err``: the fresh call (one synchronous compute and one
    window: 21 steps from the state the window left) against the float64
    replay on tiles.  ``energy_window_rel_err``: the energy of the state
    the window left against the energy of the seed's data.
    ``mode_window_rel_err``: that state's components along a few of the
    membrane's modes against the seed's data advanced by the steps counted
    (all modes together: the distance over the amplitudes).
    ``cells_unwritten``: cells of ``frame`` the fresh call left poisoned.
    ``calls_not_tiling``: calls after which the lanes' ranges did not add up
    to the membrane.  The control (``precision``) stands in the program's
    place: it replays the tiles in bfloat16, from the state handed over or,
    handed none, from the seed's data."""
    from cells import Compared

    n = int(params["n"])
    fresh = observed["fresh"]
    steps = int(observed["iterations"])
    picked = modes(cfg, seed)
    # the seed's own data: the timed path has written into the arrays
    born, _values = inputs(cfg, params, np.random.default_rng(int(seed)))
    e0, _c, c_born = sums(cfg, born["u0"], born["u1"], picked)
    del born
    if observed.get("outputs"):
        start = observed["outputs"]
        e1, c0, c1 = sums(cfg, start["u0"], start["u1"], picked)
        want0, want1, swing = mode_after(cfg, picked, c_born, steps)
        off = np.sqrt(((c0 - want0) ** 2 + (c1 - want1) ** 2).sum()
                      / (2.0 * (swing ** 2).sum()))
    else:  # the control, handed no state: the seed's data stands for it
        start, e1, off = arrays, e0, 0.0
    lim = cfg["limits"]
    out = [
        Compared("u_fresh_rel_err",
                 fresh_rel_err(cfg, start, fresh, observed["ranges_log"],
                               seed, precision),
                 float(lim["u_fresh_rel_err"])),
        Compared("energy_window_rel_err",
                 abs(e1 - e0) / e0 if e0 > 0 else float("nan"),
                 drift_limit(cfg, "energy_window_rel_err", steps)),
        Compared("mode_window_rel_err", float(off),
                 drift_limit(cfg, "mode_window_rel_err", steps)),
    ]
    if precision is None:
        poison = float(cfg["fresh_call"]["fill_value"])
        out.append(Compared(
            "cells_unwritten",
            float(np.count_nonzero(fresh["outputs"]["frame"] == poison)),
            float(lim["cells_unwritten"])))
    out.append(Compared(
        "calls_not_tiling",
        float(sum(sum(r) != n for r in observed["ranges_log"])),
        float(lim["calls_not_tiling"])))
    return out


def kernel_cost(cfg, params, items: int) -> dict:
    """One step (both kernels) over ``items`` cells.  The LEAST bytes:
    seven passes over an array's share (``waveStep`` reads ``u0`` and
    ``u1`` and writes ``frame``; ``rotate`` reads ``u1`` and ``frame`` and
    writes ``u0`` and ``u1``), float32.  Nine flop a cell: four adds and a
    multiply-subtract for the laplacian, a multiply, a subtract, a
    multiply-add for the step (``rotate`` does none)."""
    return {"ops": 9.0 * items, "bytes": 7 * 4.0 * items}
