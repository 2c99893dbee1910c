"""stream_triad: data recipe, plain reference, control, kernel cost.

The reference is ``a + s * b`` in numpy float32; with s a power of two the
product is exact, so every correctly rounded float32 implementation gives the
same bits.  It imports nothing of the program.
"""

import numpy as np


def inputs(cfg, params, rng):
    n = int(params["n"])
    arrays = {"a": rng.random(n, dtype=np.float32),
              "b": rng.random(n, dtype=np.float32),
              "c": np.zeros(n, np.float32)}
    return arrays, (float(cfg["scalar_cycle"][0]),)


def call_values(cfg, params, values):
    """s goes through a cycle of powers of two, another in every call (a
    runtime scalar of the per-call path: nothing compiles), so that ``c`` after
    the window is what the window's LAST call wrote and nothing an earlier
    call or warm-up left; the last warm-up call and the fresh call take an s
    set apart."""
    return {"cycle": [(float(s),) for s in cfg["scalar_cycle"]],
            "apart": (float(cfg["scalar_apart"]),)}


def triad(arrays, s, precision=None) -> np.ndarray:
    if precision is None:
        return arrays["a"] + np.float32(s) * arrays["b"]
    if precision == "bfloat16":
        import ml_dtypes

        bf = ml_dtypes.bfloat16
        # in blocks: numpy's bfloat16 arithmetic is slow and needs no more
        out = np.empty(arrays["a"].shape, np.float32)
        for lo in range(0, out.size, 1 << 22):
            sl = slice(lo, lo + (1 << 22))
            out[sl] = (arrays["a"][sl].astype(bf)
                       + np.asarray(s, np.float32).astype(bf)
                       * arrays["b"][sl].astype(bf)).astype(np.float32)
        return out
    raise ValueError(f"no control in precision {precision!r}")


def compare(cfg, params, arrays, values, observed, seed, precision=None):
    """``elements_differing`` over all elements, twice: ``c`` as the window
    left it against the triad with its last call's s, and ``c`` as the fresh
    call wrote it, into an array poisoned anew, with the s set apart."""
    from cells import Compared

    differing = 0
    for seen in (observed, observed["fresh"]):
        s = seen["values"][0]
        want = triad(arrays, s)
        got = (seen["outputs"]["c"] if precision is None
               else triad(arrays, s, precision))
        differing += int((got != want).sum())
    return [Compared("elements_differing", float(differing),
                     cfg["limits"]["elements_differing"])]


def kernel_cost(cfg, params, items: int) -> dict:
    """One launch over ``items`` elements: a multiply and an add each, two
    reads and one write of four bytes."""
    return {"ops": 2.0 * items, "bytes": 12.0 * items}
