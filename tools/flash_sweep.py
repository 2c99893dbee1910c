"""Flash-attention block/phase sweep on the real chip (VERDICT r4 #2).

Separates forward-only and fwd+bwd cost per (T, block_q, block_k) so the
T=8192 regression can be attributed (fwd kernel? dq kernel? dkv kernel?
block config?) instead of guessed at.

Round-6: ``default`` rows now exercise the bf16 end-to-end kernels
(f32 inputs cast once at XLA level, bf16 streamed through fwd+bwd) with
compact lse/delta operands and the causal block-DMA skip; a third
``default-bf16io`` variant feeds bf16 inputs directly, isolating the
kernel from the one-time cast.  MFU per row against the matching
roofline so block choices compare across precisions.

Methodology: the dependent chain runs INSIDE one jitted ``lax.fori_loop``
(each step perturbs the inputs by the previous step's output so XLA can
neither hoist nor dead-code-eliminate a step, and the host's per-launch
cost is paid once), closed by ``block_until_ready`` —
``workloads.fori_chain_bench``.  Needs the chip; MFU is judged against
the running device's own peak (hardware.DEVICE_PEAKS).

Usage: python tools/flash_sweep.py [T ...]
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def bench_loop(step, args, reps=8, trials=3):
    """The shared dependent-chain harness — one implementation, one place
    for the compiler traps (see its docstring)."""
    from cekirdekler_tpu.workloads import fori_chain_bench

    return fori_chain_bench(step, args, reps, trials=trials)


def main(Ts=(4096, 8192), B=1, H=8, D=64):
    from cekirdekler_tpu.ops.flash_attention import flash_attention
    from cekirdekler_tpu.parallel.attention import attention_reference
    from cekirdekler_tpu.hardware import chip_devices, device_peaks

    dev = chip_devices()[0].jax_device
    peak_bf16, _gbps, kind = device_peaks(str(dev.device_kind))
    print(f"device={kind} ({dev.platform})  B={B} H={H} D={D}")
    rng = np.random.default_rng(0)
    for T in Ts:
        mk = lambda: jnp.asarray(
            rng.standard_normal((B, T, H, D)).astype(np.float32) * 0.3)
        q, k, v = mk(), mk(), mk()
        # causal fwd+bwd FLOPs: fwd 4*T^2*D per (b,h) + bwd 12*T^2*D,
        # halved by causality
        flops = 0.5 * 16 * B * H * T * T * D
        flops_fwd = 0.5 * 4 * B * H * T * T * D

        t = bench_loop(
            lambda q, k, v: attention_reference(q, k, v, causal=True),
            (q, k, v))
        print(f"T={T} dense fwd: {t*1e3:8.2f} ms  "
              f"{flops_fwd/t/1e12:6.2f} Tflop/s")
        t = bench_loop(
            jax.grad(lambda q, k, v: attention_reference(
                q, k, v, causal=True).sum(), argnums=(0, 1, 2)),
            (q, k, v))
        print(f"T={T} dense fwd+bwd: {t*1e3:8.2f} ms  "
              f"{flops/t/1e12:6.2f} Tflop/s")

        # MFU denominators: "highest" is true-f32 multi-pass (~peak/6),
        # the bf16 variants run against the bf16 peak — the same
        # per-kind table and pass count as bench.py, so sweep MFU stays
        # comparable to the bench artifact's mfu_default
        from bench import F32_PASSES

        peaks = {"highest": peak_bf16 / F32_PASSES,
                 "default": peak_bf16,
                 "default-bf16io": peak_bf16}
        qb = kb = vb = None
        for (bq, bk) in ((256, 512), (512, 512), (512, 1024), (256, 1024),
                         (1024, 512), (1024, 1024), (128, 512)):
            for prec in ("highest", "default", "default-bf16io"):
                args, p = (q, k, v), prec
                if prec == "default-bf16io":
                    # bf16 operands in HBM: isolates the kernels from the
                    # per-call f32->bf16 cast the plain default row pays
                    if qb is None:
                        qb, kb, vb = (a.astype(jnp.bfloat16)
                                      for a in (q, k, v))
                    args, p = (qb, kb, vb), "default"
                fwd = lambda q, k, v, bq=bq, bk=bk, p=p: flash_attention(
                    q, k, v, True, bq, bk, None, p)
                g = jax.grad(
                    lambda q, k, v, bq=bq, bk=bk, p=p: flash_attention(
                        q, k, v, True, bq, bk, None, p)
                    .astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))
                try:
                    tf = bench_loop(fwd, args)
                    tg = bench_loop(g, args)
                except Exception as e:
                    print(f"T={T} flash {bq}/{bk} {prec}: FAIL "
                          f"{type(e).__name__}: {e}"[:120])
                    continue
                mfu = flops / tg / 1e12 / peaks[prec]
                print(f"T={T} flash {bq}/{bk} {prec:15s}: "
                      f"fwd {tf*1e3:8.2f} ms ({flops_fwd/tf/1e12:5.2f}) "
                      f"fwd+bwd {tg*1e3:8.2f} ms  "
                      f"{flops/tg/1e12:6.2f} Tflop/s  mfu={mfu:.3f}")


if __name__ == "__main__":
    Ts = tuple(int(a) for a in sys.argv[1:]) or (4096, 8192)
    main(Ts)
