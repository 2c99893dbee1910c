"""Dot product: the work items of a group cooperate (PR 45).

The first kernel of any OpenCL tutorial after vector-add: every work item
multiplies its pair, the work-group adds its 256 products in a ``__local``
tile with a tree (a ``barrier()`` after every halving), work item 0 writes the
group's partial, and the host adds the partials, as SHOC's level-1 Reduction
does (docs/KERNEL_LANGUAGE.md, "Work-group cooperation").  One lane: a
partial a group is a store at ``get_group_id(0)``, which is exact on one lane
and not combined across lanes (ROADMAP M12).

    python examples/dot_product.py                     # TPU chip
    JAX_PLATFORMS=cpu python examples/dot_product.py   # host CPU
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import cekirdekler_tpu as ct  # noqa: E402
from cekirdekler_tpu import ClArray  # noqa: E402
from cekirdekler_tpu.core.cruncher import NumberCruncher  # noqa: E402

N, LOCAL = 1 << 16, 256

DOT_SRC = """
__kernel void dot(__global const float* a, __global const float* b, __global float* partial) {
    __local float tile[256];
    int tid = get_local_id(0);
    tile[tid] = a[get_global_id(0)] * b[get_global_id(0)];
    barrier(CLK_LOCAL_MEM_FENCE);
    for (int s = get_local_size(0) / 2; s > 0; s >>= 1) {
        if (tid < s) { tile[tid] += tile[tid + s]; }
        barrier(CLK_LOCAL_MEM_FENCE);
    }
    // ckprove: ok one lane holds the whole range (main() takes one device)
    if (tid == 0) { partial[get_group_id(0)] = tile[0]; }
}
"""


def main() -> int:
    devs = ct.chip_devices().subset(1)
    print(f"device: {[str(d) for d in devs]}")
    rng = np.random.default_rng(0)
    # small integers: float32 sums are exact in any order
    a = ClArray(rng.integers(-3, 4, N).astype(np.float32), name="a",
                read_only=True)
    b = ClArray(rng.integers(-3, 4, N).astype(np.float32), name="b",
                read_only=True)
    partial = ClArray(np.zeros(N // LOCAL, np.float32), name="partial",
                      read=False, write=True, write_all=True)
    cr = NumberCruncher(devs, DOT_SRC)
    try:
        a.next_param(b, partial).compute(cr, 45, "dot", N, LOCAL)
        got = float(np.sum(partial.host(), dtype=np.float64))  # the host's finishing sum
        want = float(np.dot(a.host().astype(np.float64), b.host()))
        status = "OK" if got == want else "FAIL"
        print(f"dot(a, b) over {N} elements = {got:.0f} (numpy {want:.0f}) "
              f"from {N // LOCAL} partials  [{status}]")
        return 0 if got == want else 1
    finally:
        cr.dispose()


if __name__ == "__main__":
    sys.exit(main())
