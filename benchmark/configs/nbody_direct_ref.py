"""nbody_direct: data recipe, plain reference, control, kernel cost.

The reference is the kernel's mathematics written plainly in numpy float64:
for each sampled body i, a_i = sum_j d_ij / (|d_ij|^2 + eps)^(3/2) over ALL n
bodies, and one step adds a_i * dt to the velocity.  Positions never change
(the kernel reads them only), so after k steps from zero the velocity is
k * a_i * dt.  It imports nothing of the program.
"""

import numpy as np

EPS = 0.0001      # the kernel's softening term
OPS_PER_PAIR = 18  # 3 sub, 3 mul + 3 add (r2), sqrt, mul, div, 3 mul + 3 add


def inputs(cfg, params, rng):
    n = int(params["n"])
    pos = (rng.random((3, n), dtype=np.float32) - np.float32(0.5)) * np.float32(2.0)
    arrays = {"x": pos[0], "y": pos[1], "z": pos[2]}
    for name in ("vx", "vy", "vz"):
        arrays[name] = np.zeros(n, np.float32)
    return arrays, (n, float(cfg["dt"]))


def sample(cfg, params, seed: int) -> np.ndarray:
    n = int(params["n"])
    rng = np.random.default_rng([int(seed), 0x5A3])
    return np.sort(rng.choice(n, size=min(int(cfg["sample_bodies"]), n),
                              replace=False))


def step(arrays, idx, dt, precision=None) -> np.ndarray:
    """Velocity increment of one step for the bodies ``idx``: [3, len(idx)]
    float64.  ``precision="bfloat16"`` is the control: positions and every
    pairwise operation in bfloat16, the sum over j kept in float32 — the
    mildest lower-precision kernel a later PR could be tempted by."""
    if precision is None:
        dtype, acc = np.float64, np.float64
    elif precision == "bfloat16":
        import ml_dtypes

        dtype, acc = ml_dtypes.bfloat16, np.float32
    else:
        raise ValueError(f"no control in precision {precision!r}")
    px, py, pz = (arrays[k].astype(dtype) for k in "xyz")
    eps = np.asarray(EPS, dtype)
    out = np.empty((3, len(idx)), np.float64)
    for lo in range(0, len(idx), 64):  # blocks keep the pair matrix small
        sel = idx[lo:lo + 64]
        ddx = px[None, :] - px[sel, None]
        ddy = py[None, :] - py[sel, None]
        ddz = pz[None, :] - pz[sel, None]
        r2 = ddx * ddx + ddy * ddy + ddz * ddz + eps
        inv = np.asarray(1.0, dtype) / (r2 * np.sqrt(r2))
        for c, dd in enumerate((ddx, ddy, ddz)):
            out[c, lo:lo + 64] = (dd * inv).astype(acc).sum(axis=1, dtype=acc)
    return out * float(dt)


def _rel_err(got, want) -> float:
    scale = float(np.abs(want).max())
    return float(np.abs(got - want).max() / scale) if scale > 0 else float("nan")


def compare(cfg, params, arrays, values, observed, seed, precision=None):
    """Two numbers.  ``vel_step_rel_err``: the velocities after the fresh
    call (state re-zeroed, one synchronous compute, one timed call) against
    the reference — a few dozen float32 additions, so it reads the kernel's
    own precision and is what a lower-precision kernel fails.
    ``vel_window_rel_err``: the velocities the measured window left against
    iterations x the reference step.  Its error is the kernel's own, as in
    the first number, plus the drift of thousands of float32 additions of
    one increment, at most 2^-24 of the sum each: the limit is the first
    number's plus so much an iteration, and grows with the iterations a window
    holds as the drift does, so a faster program meets it as a slower one.
    It is held against a window that returns its state unchanged (reads 1)
    or drops a call of its work."""
    from cells import Compared

    idx = sample(cfg, params, seed)
    inc = step(arrays, idx, values[1])
    lim = cfg["limits"]
    if precision is None:
        end = np.stack([observed["outputs"][k][idx] for k in ("vx", "vy", "vz")])
        fresh = np.stack([observed["fresh"]["outputs"][k][idx]
                          for k in ("vx", "vy", "vz")])
    else:  # the control stands in the program's place
        low = step(arrays, idx, values[1], precision)
        end = low * observed["iterations"]
        fresh = low * observed["fresh"]["iterations"]
    return [
        Compared("vel_step_rel_err",
                 _rel_err(fresh, inc * observed["fresh"]["iterations"]),
                 lim["vel_step_rel_err"]),
        Compared("vel_window_rel_err",
                 _rel_err(end, inc * observed["iterations"]),
                 lim["vel_window_rel_err"]["at_zero"]
                 + lim["vel_window_rel_err"]["per_iteration"]
                 * observed["iterations"]),
    ]


def kernel_cost(cfg, params, items: int) -> dict:
    """Operations and bytes one launch over ``items`` bodies needs: every one
    of them against all n; positions read once, velocities read and written."""
    n = int(params["n"])
    return {"ops": float(OPS_PER_PAIR) * items * n,
            "bytes": 4.0 * (3 * n + 6 * items)}
