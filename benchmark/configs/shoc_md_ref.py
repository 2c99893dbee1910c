"""shoc_md: data recipe, plain reference, control, a call's bytes.

SHOC's level-1 MD (``src/opencl/level1/md``): one work item an atom sums the
Lennard-Jones force of its ``neighbours`` listed neighbours that lie inside the
cutoff, positions and forces ``float4`` (``w`` unused), the list laid out
``neighList[j * atoms + i]``.  The reference here is numpy in float64 and
imports nothing of the program.

SHOC draws positions uniformly in a box and finds every atom's nearest
neighbours by brute force, O(n^2), which no set-up can pay at two million
atoms.  What that recipe FIXES is kept and made in bulk (:func:`inputs`): its
density (a simple-cubic lattice of SHOC's spacing, every site jittered by less
than 0.15 spacings an axis, so no pair is closer than 0.7), every atom's
``neighbours`` nearest (the nearest lattice offsets, by length and then by the
offset's tuple), nearly all inside the cutoff (the site index wraps at the
box's faces and the DISTANCE does not, so a surface atom's wrapped neighbours
fail ``r2 < cutsq`` and both sides of the kernel's branch run), and atom
labels with no spatial order (ONE seeded permutation: ``position[jidx]`` is a
random gather).

A call is a force step: the loop ``md_step`` writes frame ``k`` of
``arrays["frames"]`` (the base positions plus the ``k``-th seeded displacement
of at most 0.01 spacings) into the bound array and computes with the ``k``-th
``(lj1, lj2)`` (:func:`call_values`); it leaves ``(k, lj1, lj2)`` of every call
it made in ``arrays["calls"]``.
"""

import numpy as np

LJ_CYCLE = ((1.5, 2.0), (1.25, 2.5), (1.75, 1.5), (2.0, 2.25))
LJ_APART = (1.0, 3.0)
SAMPLE = 262144  # atoms the comparison recomputes where there are more


def lattice(cfg) -> tuple[int, int, int]:
    nx, ny, nz = (int(v) for v in cfg["lattice"])
    if nx * ny * nz != int(cfg["atoms"]):
        raise ValueError(f"lattice {nx} x {ny} x {nz} is not {cfg['atoms']} atoms")
    return nx, ny, nz


def offsets(k: int) -> np.ndarray:
    """The ``k`` nearest lattice offsets: by length, ties by the tuple."""
    r = 1
    while (2 * r + 1) ** 3 - 1 < 8 * k:  # a cube that holds the ball
        r += 1
    g = np.arange(-r, r + 1)
    cand = np.array([(x, y, z) for x in g for y in g for z in g
                     if (x, y, z) != (0, 0, 0)], dtype=np.int64)
    order = sorted(range(len(cand)),
                   key=lambda i: (int((cand[i] ** 2).sum()), tuple(cand[i])))
    return cand[order[:k]].astype(np.int32)


def inputs(cfg, params, rng):
    n, k = int(cfg["atoms"]), int(cfg["neighbours"])
    if int(params["n"]) != n:
        raise ValueError(f"n {params['n']}: one work item an atom, {n}")
    nx, ny, nz = lattice(cfg)
    a = float(cfg["spacing"])
    perm = rng.permutation(n).astype(np.int32)  # label of site s
    site = np.empty(n, np.int32)                # site of label l
    site[perm] = np.arange(n, dtype=np.int32)
    x, rest = np.divmod(site, np.int32(ny * nz))
    y, z = np.divmod(rest, np.int32(nz))
    base = np.zeros((n, 4), np.float32)
    jitter = rng.uniform(-float(cfg["jitter"]), float(cfg["jitter"]), (n, 3))
    base[:, :3] = (np.stack([x, y, z], axis=1) + jitter) * a
    neigh = np.empty((k, n), np.int32)
    for j, (dx, dy, dz) in enumerate(offsets(k)):
        at = (((x + dx) % nx) * ny + (y + dy) % ny) * nz + (z + dz) % nz
        np.take(perm, at, out=neigh[j])
    # the frames of the cycle's calls and, last, of the one set apart
    reach = float(cfg["displacement"]) * a
    frames = np.repeat(base.reshape(1, -1), len(LJ_CYCLE) + 1, axis=0)
    shifts = rng.uniform(-reach, reach, (len(frames), n, 3)).astype(np.float32)
    frames.reshape(len(frames), n, 4)[:, :, :3] += shifts
    values = (k, float(cfg["cutsq"]), *LJ_CYCLE[0], n)
    return {"force3": np.full(4 * n, -1.0, np.float32),
            "position": frames[0].copy(), "neighList": neigh.reshape(-1),
            # not kernel arguments: the positions call by call, the loop's log
            "frames": frames, "calls": []}, values


def call_values(cfg, params, values):
    """The kernel's scalar arguments call by call: ``(lj1, lj2)`` goes through
    a cycle of four pairs, SHOC's (1.5, 2.0) first, and one pair apart."""
    k, cutsq, _lj1, _lj2, n = values
    return {"cycle": [[k, cutsq, *lj, n] for lj in LJ_CYCLE],
            "apart": [k, cutsq, *LJ_APART, n]}


def _bf16(x):
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def forces(position, neigh, atoms, cutsq, lj1, lj2, rounding=None,
           block: int = 16384, without_cutoff: bool = False):
    """``[len(atoms), 3]`` float64: the kernel's sum for the atoms ``atoms``,
    from ``position`` (``[n, 4]``) and ``neigh`` (``[k, n]``).  ``rounding``:
    a function applied to the positions and to every product (the control's
    lower precision); None: float64 throughout."""
    rd = rounding or (lambda v: v)
    pos = rd(np.asarray(position, np.float64).reshape(-1, 4)[:, :3])
    out = np.empty((len(atoms), 3), np.float64)
    for b0 in range(0, len(atoms), block):
        idx = atoms[b0:b0 + block]
        jdx = neigh[:, idx]                               # [k, b]
        d = pos[idx][None, :, :] - pos[jdx]               # [k, b, 3]
        r2 = rd(rd(d[..., 0] * d[..., 0]) + rd(d[..., 1] * d[..., 1])
                + rd(d[..., 2] * d[..., 2]))
        inside = np.ones_like(r2, bool) if without_cutoff else r2 < cutsq
        with np.errstate(divide="ignore", invalid="ignore"):
            r2inv = rd(1.0 / r2)
            r6inv = rd(rd(r2inv * r2inv) * r2inv)
            force = rd(rd(r2inv * r6inv) * rd(rd(lj1 * r6inv) - lj2))
        term = np.where(inside[..., None], rd(d * force[..., None]), 0.0)
        acc = np.zeros((len(idx), 3), np.float64)
        for j in range(term.shape[0]):  # the kernel's order of accumulation
            acc = rd(acc + term[j])
        out[b0:b0 + block] = acc
    return out


def kernel_cost(cfg, params, items):
    """The least a call moves and computes, whatever lowers it: every entry
    of the list read once, ONE 16-byte position a neighbour, an atom's own
    position read and its force written; 3 + 5 + 1 + 1 + 2 + 4 + 6 = 22
    floating-point operations a pair inside the cutoff."""
    n, k = int(items), int(cfg["neighbours"])
    return {"ops": 22 * k * n, "bytes": 4 * k * n + 16 * k * n + 32 * n}


def compare(cfg, params, arrays, values, observed, seed, precision=None):
    """Four numbers.  ``force_window_rel_err`` / ``force_fresh_rel_err``: the
    largest error of a force's x, y, z over the largest value, for the atoms
    recomputed (every atom up to 262 144, else a seeded 262 144), after the
    window's last call and after the fresh call (into ``force3`` poisoned
    anew), each against ITS frame and ITS ``(lj1, lj2)``.  ``atoms_unwritten``:
    atoms (all of them) whose force still holds the poison; ``w_nonzero``:
    atoms whose ``w`` is not the 0 the kernel stores: both exact.  The control
    (``precision``: "bfloat16") stands in the program's place with the
    positions and every product rounded to bfloat16."""
    from cells import Compared

    lim = cfg["limits"]
    n, k = int(cfg["atoms"]), int(cfg["neighbours"])
    neigh = arrays["neighList"].reshape(k, n)
    frames = arrays["frames"].reshape(-1, n, 4)
    atoms = (np.arange(n) if n <= SAMPLE else np.sort(
        np.random.default_rng([int(seed), 48]).choice(n, SAMPLE, replace=False)))
    pairs = [tuple(float(v) for v in lj) for lj in LJ_CYCLE + (LJ_APART,)]
    poison = float(cfg["fresh_call"]["fill_value"])
    errs, unwritten, w_nonzero = [], 0, 0
    for seen in (observed, observed["fresh"]):
        _k, cutsq, lj1, lj2, _n = (float(v) for v in seen["values"])
        frame = frames[pairs.index((lj1, lj2))]
        want = forces(frame, neigh, atoms, cutsq, lj1, lj2)
        if precision is None:
            out = np.asarray(seen["outputs"]["force3"]).reshape(n, 4)
            got = out[atoms, :3].astype(np.float64)
            unwritten += int(np.all(out == poison, axis=1).sum())
            w_nonzero += int((out[:, 3] != 0).sum())
        elif precision == "bfloat16":
            got = forces(frame, neigh, atoms, cutsq, lj1, lj2, rounding=_bf16)
        else:
            raise ValueError(f"no control in precision {precision!r}")
        errs.append(float(np.abs(got - want).max() / np.abs(want).max()))
    return [Compared("force_window_rel_err", errs[0], lim["force_rel_err"]),
            Compared("force_fresh_rel_err", errs[1], lim["force_rel_err"]),
            Compared("atoms_unwritten", float(unwritten), lim["atoms_unwritten"]),
            Compared("w_nonzero", float(w_nonzero), lim["w_nonzero"])]
