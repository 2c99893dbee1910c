"""mandelbrot_display: data recipe, plain reference, control.

The demo as its users run it: a frame a call, every frame looked at on the
host.  The reference is the kernel's orbit in numpy float32, operation for
operation (the counts are exact only if every multiply and add rounds as the
kernel's do), on seeded blocks of pixels.  It imports nothing of the program
and is this configuration's own copy (``mandelbrot_frame_ref.py`` is the
four-chip configuration's).

Every seed renders the same frames; the seed draws the blocks compared.  A
traffic file's ``view_cycle`` says how many views the window's calls go
through: view k is the configuration's shifted by k quarters of a pixel in x
and y, as a renderer's view pans.  The kernel's scalars are run-time
arguments of a per-call launch, so the pan costs no compile there; a fused
window bakes them, so the windowed cell keeps ``view_cycle`` 1.  What a call
must leave in the caller's array is ITS view's frame: the frame of the call
before differs from it along the whole boundary of the set.
"""

import numpy as np


def inputs(cfg, params, rng):
    w, h = int(cfg["width"]), int(cfg["height"])
    view = cfg["view"]
    dx = np.float32(view["extent"] / w)
    dy = np.float32(view["extent"] / h)
    values = (float(np.float32(view["x0"])), float(np.float32(view["y0"])),
              float(dx), float(dy), w, int(cfg["max_iter"]))
    return {"out": np.full(w * h, -1.0, np.float32)}, values


def shifted(values, pixels: float, max_iter=None) -> tuple:
    """``values`` with the view moved by ``pixels`` in x and y (float32, as
    the kernel takes them)."""
    x0, y0, dx, dy, w, it = values
    return (float(np.float32(x0 + pixels * dx)),
            float(np.float32(y0 + pixels * dy)),
            dx, dy, w, it if max_iter is None else max_iter)


def call_values(cfg, params, values):
    """The window's calls go through ``view_cycle`` views, a quarter of a
    pixel apart.  The view set apart (the last warm-up call and the fresh
    call) is shifted by half a pixel and stops one iteration earlier: the same
    cost to within half a percent, and another count in every pixel of the
    set's interior and along its boundary."""
    cycle = [shifted(values, k / 4.0)
             for k in range(int(params.get("view_cycle", 1)))]
    return {"cycle": cycle,
            "apart": shifted(values, 0.5, int(values[5]) - 1)}


def sample(cfg, params, seed: int) -> np.ndarray:
    """Pixel indices of the seeded blocks (one block = one local range)."""
    lr = int(cfg["local_range"])
    blocks = int(cfg["width"]) * int(cfg["height"]) // lr
    rng = np.random.default_rng([int(seed), 0xB10C])
    pick = np.sort(rng.choice(blocks, size=min(int(cfg["sample_blocks"]),
                                               blocks), replace=False))
    return (pick[:, None] * lr + np.arange(lr)[None, :]).reshape(-1)


def orbit(values, px: np.ndarray, precision=None) -> np.ndarray:
    """Escape-iteration counts of the pixels ``px``, as float32."""
    if precision is None:
        dtype = np.float32
    elif precision == "bfloat16":
        import ml_dtypes

        dtype = ml_dtypes.bfloat16
    else:
        raise ValueError(f"no control in precision {precision!r}")
    x0, y0, dx, dy, width, max_iter = values
    f = lambda v: np.asarray(v, np.float32).astype(dtype)
    cx = f(x0) + f(dx) * (px % width).astype(np.float32).astype(dtype)
    cy = f(y0) + f(dy) * (px // width).astype(np.float32).astype(dtype)
    zx = np.zeros_like(cx)
    zy = np.zeros_like(cy)
    it = np.zeros(px.shape, np.int32)
    active = np.ones(px.shape, bool)
    four, two = f(4.0), f(2.0)
    for _ in range(int(max_iter)):
        zx2, zy2 = zx * zx, zy * zy
        active &= (zx2 + zy2) < four
        if not active.any():
            break
        t = zx2 - zy2 + cx
        zy = np.where(active, two * zx * zy + cy, zy)
        zx = np.where(active, t, zx)
        it += active
    return it.astype(np.float32)


def compare(cfg, params, arrays, values, observed, seed, precision=None):
    """Three numbers.  ``pixels_differing``: the sampled blocks of the frame
    the window's LAST call left against the orbit for that call's own view,
    and of the frame the fresh call wrote against the orbit for the view set
    apart.  ``pixels_unwritten``: pixels of either frame still at the
    sentinel; the fresh call writes into a frame poisoned anew, so one call's
    range and read-back have to cover every pixel.  ``calls_not_tiling``:
    calls of the window after which the lanes' ranges did not add up to the
    frame."""
    from cells import Compared

    px = sample(cfg, params, seed)
    lim = cfg["limits"]
    frames = [(observed["values"], observed["outputs"]),
              (observed["fresh"]["values"], observed["fresh"]["outputs"])]
    differing = unwritten = 0
    for vals, outputs in frames:
        want = orbit(vals, px)
        if precision is None:
            got = outputs["out"][px]
            unwritten += int((outputs["out"] < 0).sum())
        else:  # the control stands in the program's place
            got = orbit(vals, px, precision)
        differing += int((got != want).sum())
    n = int(cfg["width"]) * int(cfg["height"])
    not_tiling = sum(sum(r) != n for r in observed["ranges_log"])
    return [
        Compared("pixels_differing", float(differing),
                 lim["pixels_differing"]),
        Compared("pixels_unwritten", float(unwritten),
                 lim["pixels_unwritten"]),
        Compared("calls_not_tiling", float(not_tiling),
                 lim["calls_not_tiling"]),
    ]
