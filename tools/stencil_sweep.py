"""Stencil tap-count sweep: is there a K where the Pallas halo path beats
the fused XLA lowering? (VERDICT r4 #6)

Generates a K-tap 1-D stencil kernel (K shifted loads per store, one halo
fetch amortized across all K), lowers it both ways, and measures with the
faceoff chain methodology (dependent fori_loop steps, one device fence
— workloads.fori_chain_bench; needs the chip).  The answer feeds docs/KERNEL_LANGUAGE.md's routing section.

Usage: python tools/stencil_sweep.py [K ...]
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp
import numpy as np


def stencil_src(taps: list[int]) -> str:
    terms = " + ".join(f"p[i{t:+d}]" for t in taps)
    return (
        "__kernel void sten(__global float* p, __global float* q) "
        "{ int i = get_global_id(0); "
        f"q[i] = 0.9f*p[i] + {1.0/ max(len(taps),1):.6f}f*({terms}); }}"
    )


def bench(fn, arrs, reps):
    """Shared harness, structural carry: the stencil output feeds back as
    the next input (q becomes p) — see fori_chain_bench's carry arg."""
    from cekirdekler_tpu.workloads import fori_chain_bench

    return fori_chain_bench(
        lambda *c: fn(0, c, ()),
        arrs,
        reps,
        carry=lambda c, out: (out[1], c[0]),
    )


def main(Ks=(2, 4, 8, 16, 24), n=1 << 24, reps=192):
    from cekirdekler_tpu.kernel import codegen, lang
    from cekirdekler_tpu.kernel.pallas_backend import build_kernel_fn_pallas
    from cekirdekler_tpu.hardware import chip_devices

    print(f"device={chip_devices()[0].name} n={n} reps={reps}")
    rng = np.random.default_rng(0)
    base = (
        jnp.asarray(rng.standard_normal(n).astype(np.float32)),
        jnp.zeros(n, jnp.float32),
    )
    for K in Ks:
        # K taps split between rows (±128 strides) and lanes (±1..)
        taps = []
        for d in range(1, K // 2 + 1):
            taps.append(d if d % 2 else 128 * (d // 2))
            taps.append(-(d if d % 2 else 128 * (d // 2)))
        taps = sorted(set(taps))[:K]
        src = stencil_src(taps)
        kdef = {k.name: k for k in lang.parse_kernels(src)}["sten"]
        xla_fn, _ = codegen.build_kernel_fn(kdef, n, 256, n)
        try:
            pl_fn, _ = build_kernel_fn_pallas(kdef, n, 256, n, force=True)
        except Exception as e:
            print(f"K={K}: pallas build failed: {e}"[:120])
            continue
        tx = bench(xla_fn, base, reps)
        tp = bench(pl_fn, base, reps)
        gbps = 3 * 4 * n / tx / 1e9
        print(f"K={len(taps)} taps={taps[:6]}...: xla {tx*1e3:7.3f} ms "
              f"({gbps:5.0f} GB/s)  pallas {tp*1e3:7.3f} ms  "
              f"ratio x/p {tx/tp:.2f}")


if __name__ == "__main__":
    Ks = tuple(int(a) for a in sys.argv[1:]) or (2, 4, 8, 16, 24)
    main(Ks)
