#!/usr/bin/env python3
"""Time, on the chip and alone, the parts the XLA lowering makes a CSR
product of (``cekirdekler_tpu/kernel/codegen.py``): the refill of a run
window (``_run_window``), a per-lane read through 128-wide rows
(``_take_rows``) and the plain ``jnp.take`` both replace.  At the sizes of
``spmv_hpcg256_window``: 2^22 lanes (its largest rung), a table of the grid's
nonzeros read at stride 27 (``col`` / ``val``: a row's run starts 27 after
its neighbour's) and a table of the grid's rows (``x``) read near (a
neighbouring grid plane) and at random:

    python3 benchmark/checks/spmv_parts.py [--side 192] [--lanes 4194304]

Prints ns a lane for each part (the least of ``--reps`` calls; a call of
``_run_window`` builds its row view, as a launch does) and the two views'
own times.  A check, not a cell: it reports no metric and is listed nowhere
in ``BENCHMARK.json``; a change to the lowering's reads starts from these
numbers (PERF.md s.6).  Exits 3 without a TPU.
"""

import argparse
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--side", type=int, default=192)
    ap.add_argument("--lanes", type=int, default=1 << 22)
    ap.add_argument("--reps", type=int, default=4)
    args = ap.parse_args(argv)

    import numpy as np

    import jax
    import jax.numpy as jnp

    from cekirdekler_tpu.kernel import codegen

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU chip", file=sys.stderr)
        return 3
    lanes, side = args.lanes, args.side
    rows, nonzeros = side ** 3, (3 * side - 2) ** 3

    def ctx_of(buf):
        ctx = codegen._Ctx(8, 0, 8, 8, {})
        ctx.bufs["t"] = buf
        return ctx

    parts = {
        "_run_window": lambda t, j: codegen._run_window(ctx_of(t), "t", j),
        "_take_rows": lambda t, j: codegen._take_rows(ctx_of(t), "t", j),
        "jnp.take": lambda t, j: jnp.take(t, j, mode="clip"),
        "view, plain": lambda t, j: ctx_of(t).rows_view("t"),
        "view, overlapping": lambda t, j: ctx_of(t).rows_view("t", True),
    }

    def time_of(part, table, index) -> float:
        fn = jax.jit(parts[part])
        jax.block_until_ready(fn(table, index))
        walls = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(table, index))
            walls.append(time.perf_counter() - t0)
        return min(walls)

    def report(part, table, index, what) -> None:
        wall = time_of(part, table, index)
        print(f"{part:18s} {what:44s} {wall * 1e3:9.2f} ms "
              f"{wall * 1e9 / lanes:8.2f} ns a lane", flush=True)

    print(f"{jax.devices()[0].device_kind}; {lanes} lanes, grid {side}^3",
          flush=True)
    table = jnp.arange(nonzeros, dtype=jnp.int32)
    stride27 = jnp.arange(lanes, dtype=jnp.int32) * 27
    what = f"{nonzeros} elements at stride 27"
    for part in ("_run_window", "_take_rows", "jnp.take"):
        report(part, table, stride27, what)
    for part in ("view, plain", "view, overlapping"):
        wall = time_of(part, table, stride27)
        print(f"{part:18s} {str(nonzeros) + ' elements':44s} "
              f"{wall * 1e3:9.2f} ms", flush=True)
    del table
    x = jnp.arange(rows, dtype=jnp.float32)
    plane = jnp.clip(jnp.arange(lanes, dtype=jnp.int32) + side * side + 1,
                     0, rows - 1)
    anywhere = jnp.asarray(np.random.default_rng(0).integers(
        0, rows, lanes, dtype=np.int32))
    for index, how in ((plane, "near"), (anywhere, "at random")):
        for part in ("_run_window", "_take_rows", "jnp.take"):
            report(part, x, index, f"{rows} elements {how}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
