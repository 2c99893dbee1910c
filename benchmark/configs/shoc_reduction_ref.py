"""shoc_reduction: data recipe, plain reference, control, a call's bytes.

SHOC's level-1 Reduction (``src/opencl/level1/reduction``): ``groups`` x
``local_range`` work items walk ``g_idata`` with the grid's stride, two elements
a pass; a work-group's partial is the sum of the elements its work items meet
below the call's ``n``, and the host adds the partials.  The reference here is
numpy and imports nothing of the program: which elements the kernel's indexing
hands to which GROUP is written out once (:func:`partials`), in float64.

The data are integers 0, 1, 2 as float32 (SHOC fills ``i % 3``; ``--seed`` draws
them), so a group's partial is an integer far below 2**24 and float32 addition
is exact in any order: every limit is a count.  ``n`` changes call by call
(:func:`call_values`), every call's ``n`` other than its neighbours', so the
partials and the sum of a call are no other call's.  The loop ``reduction``
leaves ``(n, sum)`` of every call it made in ``arrays["sums"]``.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

PIECES = 64  # of the input array, each drawn from a generator of its own


def geometry(cfg, params) -> tuple[int, int]:
    """``(groups, local range)`` of the launch."""
    local = int(cfg["local_range"])
    return int(params["n"]) // local, local


def inputs(cfg, params, rng):
    elements = int(cfg["elements"])
    groups, local = geometry(cfg, params)
    if elements % (2 * local):
        raise ValueError(f"elements {elements}: no whole number of the "
                         f"{2 * local} elements a group takes a pass")
    # 1 GiB of float32 is mostly page faults for one thread (set-up): the
    # array is drawn in PIECES pieces, each from its own child of the seed's
    # generator, by a few threads.  The pieces are fixed, so a seed's data
    # are the same on any host
    data = np.empty(elements, np.float32)
    bounds = np.linspace(0, elements, PIECES + 1).astype(np.int64)

    def fill(k: int, child) -> None:
        lo, hi = bounds[k], bounds[k + 1]
        data[lo:hi] = child.integers(0, 3, hi - lo, dtype=np.uint8)

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, range(PIECES), rng.spawn(PIECES)))
    return {"g_idata": data, "g_odata": np.full(groups, -1.0, np.float32),
            # not a kernel argument: the loop's log, (n, sum) a call
            "sums": []}, (elements,)


def call_values(cfg, params, values):
    """The kernel's ``n`` call by call: the whole array less 0, 1, 2, 3 passes
    of the grid for the window's calls, less 8 for the last warm-up call and
    the fresh call."""
    elements = int(cfg["elements"])
    grid = 2 * int(params["n"])
    return {"cycle": [[elements - k * grid] for k in range(4)],
            "apart": [elements - 8 * grid]}


def partials(x, n: int, groups: int, local: int, first_pass: int = 0):
    """What each group's partial has to be, in float64: work item ``t`` of
    group ``g`` starts at ``i = 2 * local * g + t`` and, while ``i < n``, adds
    ``x[i] + x[i + local]`` and moves on by the grid (``2 * local * groups``).
    Passes from ``first_pass`` on."""
    block, grid = 2 * local, 2 * local * groups
    whole = max(int(n) // grid, first_pass)
    out = x[first_pass * grid:whole * grid].reshape(-1, groups, block).sum(
        axis=(0, 2), dtype=np.float64)
    tail = x[whole * grid:(whole + 1) * grid]
    if n > whole * grid and tail.size:
        j = np.arange(tail.size) + whole * grid
        i = np.where(j % block < local, j, j - local)  # the pass's own ``i``
        np.add.at(out, (j % grid) // block,
                  np.where(i < n, tail, 0).astype(np.float64))
    return out


def _bf16(x):
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def partials_bfloat16(x, n: int, groups: int, local: int):
    """The control: the kernel as written with the tile in bfloat16 (every
    ``sdata[...] +=`` rounds to bfloat16: the accumulation and the tree), the
    mildest lower precision a later PR could be tempted by.  A work item's
    8192 terms of 0 to 4 stop moving a bfloat16 sum at 512."""
    block, grid = 2 * local, 2 * local * groups
    passes = -(-int(n) // grid)
    acc = np.zeros((groups, local), np.float32)
    for k in range(passes):
        rows = x[k * grid:(k + 1) * grid].reshape(groups, block)
        i = k * grid + block * np.arange(groups)[:, None] + np.arange(local)
        acc = np.where(i < n, _bf16(acc + _bf16(rows[:, :local] + rows[:, local:])),
                       acc)
    s = local // 2
    while s:
        acc[:, :s] = _bf16(acc[:, :s] + acc[:, s:2 * s])
        s //= 2
    return acc[:, 0].astype(np.float64)


def kernel_cost(cfg, params, items, n=None):
    """The least a call of ``n`` elements moves and adds, whatever lowers it:
    every element read once, one partial a group written."""
    n = int(cfg["elements"] if n is None else n)
    groups, _local = geometry(cfg, params)
    return {"ops": n, "bytes": 4 * n + 4 * groups}


def compare(cfg, params, arrays, values, observed, seed, precision=None):
    """Three counts, all exact.  ``partials_differing``: partials in the
    caller's array that are not the float64 sum of exactly the elements the
    kernel's indexing gives that group below THAT call's ``n``, after the
    window's last call and after the fresh call.  ``sum_abs_err``: the largest
    distance of a sum the loop handed its caller (logged call by call) from the
    float64 sum of its call's prefix.  ``partials_unwritten``: partials still
    holding the poison.  The control (``precision``: "bfloat16") stands in the
    program's place with :func:`partials_bfloat16`."""
    from cells import Compared

    lim = cfg["limits"]
    groups, local = geometry(cfg, params)
    x, grid = arrays["g_idata"], 2 * local * groups
    want: dict = {}

    def wanted(n: int):
        if n not in want:
            # the calls' prefixes differ by a few passes: sum the array once
            base = min(want, default=None)
            if base is not None and base % grid == 0 and n > base:
                want[n] = want[base] + partials(x, n, groups, local, base // grid)
            else:
                want[n] = partials(x, n, groups, local)
        return want[n]

    calls = [(int(observed["values"][0]), observed["outputs"]),
             (int(observed["fresh"]["values"][0]), observed["fresh"]["outputs"])]
    wanted(min(n for n, _ in calls))
    if precision is None:
        got = [(n, out["g_odata"].astype(np.float64)) for n, out in calls]
        log = [(int(n), float(s)) for n, s in arrays["sums"]]
    elif precision == "bfloat16":
        got = [(n, partials_bfloat16(x, n, groups, local)) for n, _ in calls]
        log = [(n, float(p.sum())) for n, p in got]
    else:
        raise ValueError(f"no control in precision {precision!r}")
    poison = float(cfg["fresh_call"]["fill_value"])
    differing = sum(int((p != wanted(n)).sum()) for n, p in got)
    unwritten = sum(int((p == poison).sum()) for _n, p in got)
    # a loop that logged nothing handed its caller nothing: no number, not 0
    err = max((abs(s - float(wanted(n).sum())) for n, s in log),
              default=float("nan"))
    return [Compared("partials_differing", float(differing),
                     lim["partials_differing"]),
            Compared("sum_abs_err", float(err), lim["sum_abs_err"]),
            Compared("partials_unwritten", float(unwritten),
                     lim["partials_unwritten"])]
