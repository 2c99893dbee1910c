"""A ``float[4n]`` bound to a ``float4*`` parameter (PR 50).

Upstream's wiki binds a plain array to a ``float4`` kernel parameter by
setting ``numberOfElementsPerWorkItem = 4`` on the ``ClArray``: one work item
owns four consecutive elements, and a ranged transfer moves four elements a
work item.  The port's flag is ``elements_per_work_item``; the kernel language
takes ``float4`` as a type (docs/KERNEL_LANGUAGE.md, "Vector types"): one
load, one store a vector, componentwise arithmetic, ``.x .y .z .w``.

    python examples/float4_saxpy.py                     # TPU chip
    JAX_PLATFORMS=cpu python examples/float4_saxpy.py   # host CPU
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import cekirdekler_tpu as ct  # noqa: E402
from cekirdekler_tpu import ClArray  # noqa: E402
from cekirdekler_tpu.core.cruncher import NumberCruncher  # noqa: E402

N, LOCAL = 1 << 16, 256  # work items: one float4 each

SAXPY4_SRC = """
__kernel void saxpy4(__global const float4* x, __global float4* y, float a) {
    int i = get_global_id(0);
    float4 v = a * x[i] + y[i];
    v.w = x[i].w;            // w rides along unscaled
    y[i] = v;
}
"""


def main() -> int:
    devs = ct.chip_devices().subset(1)
    print(f"device: {[str(d) for d in devs]}")
    rng = np.random.default_rng(0)
    # a float[4 N] each; small integers, so float32 is exact
    x = ClArray(rng.integers(-8, 9, 4 * N).astype(np.float32), name="x",
                partial_read=True, read_only=True, elements_per_work_item=4)
    y0 = rng.integers(-8, 9, 4 * N).astype(np.float32)
    y = ClArray(y0.copy(), name="y", partial_read=True,
                elements_per_work_item=4)
    cr = NumberCruncher(devs, SAXPY4_SRC)
    try:
        x.next_param(y).compute(cr, 48, "saxpy4", N, LOCAL, values=(3.0,))
        want = (3.0 * x.host() + y0).reshape(N, 4)
        want[:, 3] = x.host().reshape(N, 4)[:, 3]
        ok = np.array_equal(y.host().reshape(N, 4), want)
        print(f"saxpy4 over {N} float4 ({4 * N} floats): "
              f"y[0] = {y.host()[:4].tolist()}  [{'OK' if ok else 'FAIL'}]")
        return 0 if ok else 1
    finally:
        cr.dispose()


if __name__ == "__main__":
    sys.exit(main())
