"""Flash attention — Pallas TPU kernel for the transformer's hot op.

The framework's attention tier so far: a dense jnp reference
(parallel/attention.py:attention_reference) and the ring/Ulysses
sequence-parallel forms whose INNER block math is plain XLA einsums.  This
module adds the single-chip hot op those forms sit on: a tiled
flash-attention forward in Pallas — Q blocks resident in VMEM, K/V streamed
block-by-block with a running stable-softmax (max/denominator carries), so
attention memory is O(block²) instead of O(T²) and the MXU runs back-to-back
``q·kᵀ`` / ``p·v`` contractions without materializing scores in HBM.

Causal masking skips fully-masked K blocks entirely (the loop bound per Q
block is derived from its last query position), halving causal work.  In
the single-chip kernels the BlockSpec index maps additionally CLAMP the
streamed operand's block index to the last live block on masked grid
steps, so the skipped step issues no new DMA either — without the clamp a
dense causal grid still moves every K/V (or Q/dO) block through HBM twice
over, and the bwd kernels are bandwidth-bound (r6 MFU work).

Kernel dtype policy (r6): the kernels contract in the OPERANDS' dtype with
f32 accumulators (``preferred_element_type``), instead of casting every
block to f32 in-kernel.  ``precision="default"`` on f32 inputs casts
q/k/v (and dO in the backward) to bf16 ONCE at the XLA level, so the
kernels stream HALF the HBM bytes — the bytes bf16 training would actually
move — while the softmax statistics, accumulators and emitted gradients
stay f32.  ``precision="highest"`` still streams f32 and runs true-f32
(multi-pass) MXU contractions, matching the dense reference to ~5e-5.

Gradients: ``flash_attention`` carries a ``jax.custom_vjp`` whose backward
is ALSO tiled Pallas (FlashAttention-2 structure): the forward saves the
per-row logsumexp, the backward recomputes each score block from it (the
flash trade — FLOPs for memory) and runs two kernels, one accumulating dq
across k blocks and one accumulating dk/dv across q blocks, so training
memory stays O(T) + O(block²) — the full [T, T] probability matrix is
never materialized in either direction.  The logsumexp residual and the
``delta = rowsum(dO ∘ O)`` operand ride compact ``[B*H, T, 1]`` columns
end-to-end (forward kernel emits, backward kernels consume) — never the
``[bq, 128]`` lane-broadcast tiles of r5 that carried 128× the bytes.

Mosaic constraints mirror ops/mandelbrot.py: no ±inf mask arithmetic in the
carry path (a −1e30 additive mask keeps every exp finite) and accumulators
derived from computed values, not constants.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .platform import call_by_platform

__all__ = ["flash_attention", "flash_attention_parts",
           "flash_attention_bwd_parts", "auto_block", "default_blocks",
           "fused_qkv", "fused_qkv_attention"]

_NEG = -1e30  # finite "-inf": exp(_NEG - m) == 0 without nan hazards

# Smallest block the MXU fills a full 128-lane tile with: below this the
# per-block softmax VPU work dominates and dense XLA attention wins (the
# auto_block docstring's measured cliff) — default-argument calls fall
# back to dense rather than run sub-128 tiles.
_DENSE_FLOOR = 128


def auto_block(T: int, target: int = 512, floor: int = 8) -> int | None:
    """Largest power-of-two block ≤ ``target`` dividing ``T``, or None when
    only degenerate tiles (< ``floor``) divide it — callers should fall
    back to dense attention then (a (1, D)-tile grid of T² steps is far
    slower than the dense einsum it replaces).

    The 512 default target comes from an on-chip block sweep (T=4096,
    D=64, f32): small 128² blocks leave the MXU ~6% utilized (the
    per-block softmax VPU work dominates); 256-1024 element blocks are
    1.5-3x faster, with q=512/k=512 the fwd+bwd sweet spot (r5
    full-gradient sweep)."""
    blk = math.gcd(T, target)
    return blk if blk >= floor else None


def default_blocks(Tq: int, Tk: int | None = None,
                   target: int = 512) -> tuple[int, int] | None:
    """Block policy for DEFAULT-argument :func:`flash_attention` calls:
    the measured 512 target degraded by gcd, or ``None`` — meaning "run
    dense attention" — when only sub-128 (sub-MXU-tile) blocks divide a
    sequence length (e.g. T=96 → 32, T=4104 → 8).  Callers that pass
    blocks explicitly keep the strict :func:`_blocks_for` contract
    (degrade to its floor, then raise)."""
    Tk = Tq if Tk is None else Tk
    bq = math.gcd(Tq, target)
    bk = math.gcd(Tk, target)
    if min(bq, bk) < _DENSE_FLOOR:
        return None
    return bq, bk


def _fa_kernel(*refs, scale, block_q, block_k, n_kb, causal, precision,
               parts=False, with_lse=False):
    """One (bh, q-block, k-block) grid step.

    The k dimension is the MINOR grid axis: Pallas runs it sequentially per
    q block and auto-pipelines the K/V block DMA behind compute (double
    buffering — the kernel never holds more than one K/V block in VMEM, so
    sequence length is unbounded).  Running max / denominator / output
    accumulate in VMEM scratch across the k steps; the final k step
    normalizes into the output block.

    Contractions run in the operands' dtype (bf16 inputs → single-pass
    bf16 MXU) with f32 accumulators; the probability block is cast to the
    V dtype for the second contraction — the standard flash trade.  The
    scale folds into the f32 score block after the first contraction, so
    no operand needs an in-kernel cast.

    ``parts=True`` is the ring-attention inner form: two extra SMEM scalars
    (global position offsets of this chip's Q and the in-flight K/V block,
    runtime values — the ring rotates them) shift the causal mask, and the
    kernel emits the UNNORMALIZED accumulator plus running max/denominator
    so ring steps merge stable-softmax state across chips."""
    if parts:
        q_off_ref, k_off_ref = refs[0], refs[1]
        q_ref, k_ref, v_ref = refs[2:5]
        o_ref, m_ref, l_ref = refs[5:8]
        m_scr, l_scr, acc_scr = refs[8:]
        q_pos0 = q_off_ref[0, 0]
        k_pos0 = k_off_ref[0, 0]
    elif with_lse:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs[:5]
        m_scr, l_scr, acc_scr = refs[5:]
        q_pos0 = k_pos0 = 0
    else:
        q_ref, k_ref, v_ref, o_ref = refs[:4]
        m_scr, l_scr, acc_scr = refs[4:]
        q_pos0 = k_pos0 = 0
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: the last query of block qi attends keys at global positions
    # <= its own; blocks wholly beyond that are skipped (no FLOPs, and in
    # the non-parts kernels the clamped index map re-targets the same
    # live block so no DMA moves either)
    live = (
        (k_pos0 + kj * block_k <= q_pos0 + qi * block_q + block_q - 1)
        if causal
        else True
    )

    @pl.when(live)
    def _step():
        q = q_ref[0]                                  # (bq, D), native dtype
        kb = k_ref[0]                                 # (bk, D)
        vb = v_ref[0]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        ) * scale                                     # (bq, bk) f32
        if causal:
            q_pos = q_pos0 + qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = k_pos0 + kj * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(k_pos <= q_pos, s, _NEG)
        one_shot = n_kb == 1 and not parts
        if one_shot:
            # single k block: the running-max state is degenerate
            # (m_prev == _NEG, alpha == 1, acc == 0), so the softmax
            # one-shots — no scratch read, no rescale multiply, no
            # accumulate add.  Value-identical to the running form:
            # max(_NEG, s.max) == s.max and 0·1 + dot == dot.  The
            # tuner selects this variant whenever it engages
            # block_k == Tk.
            m_new = s.max(axis=-1)
        else:
            m_prev = m_scr[:, 0]
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        # "highest": keep p f32 (upcast v); "default": p joins the
        # operands' (bf16) MXU pass — the standard flash trade
        if precision == lax.Precision.HIGHEST:
            p2, vb2 = p, vb.astype(jnp.float32)
        else:
            p2, vb2 = p.astype(vb.dtype), vb
        dot = jax.lax.dot_general(
            p2, vb2, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        if one_shot:
            acc_scr[...] = dot
            l_scr[:, 0] = p.sum(axis=-1)
        else:
            alpha = jnp.exp(m_prev - m_new)
            acc_scr[...] = acc_scr[...] * alpha[:, None] + dot
            l_scr[:, 0] = l_scr[:, 0] * alpha + p.sum(axis=-1)
        m_scr[:, 0] = m_new

    @pl.when(kj == n_kb - 1)
    def _finish():
        if parts:
            o_ref[0] = acc_scr[...]
            m_ref[0] = m_scr[...]
            l_ref[0] = l_scr[...]
        else:
            o_ref[0] = (
                acc_scr[...] / jnp.maximum(l_scr[:, 0], 1e-30)[:, None]
            ).astype(o_ref.dtype)
            if with_lse:
                lse_ref[0] = m_scr[...] + jnp.log(
                    jnp.maximum(l_scr[...], 1e-30)
                )



def _resolve(interpret, precision):
    """One place for the precision-string -> lax.Precision mapping — used
    by the primal, parts, fwd, and bwd paths so they can never diverge.
    ``interpret`` passes through untouched: ``None`` means "lower per
    dispatch platform" and is resolved at each ``pallas_call``
    (ops/platform.py), never from the process's default backend."""
    precision = _precision_str(precision)  # validate enum/string spellings
    prec = (
        lax.Precision.HIGHEST if precision == "highest"
        else lax.Precision.DEFAULT
    )
    return interpret, prec


def _precision_str(precision) -> str:
    """Normalize a precision spelling to the module's canonical strings —
    ``lax.Precision.DEFAULT`` and ``"default"`` must select the SAME
    path (``_stream_cast`` keys on the string; an enum slipping through
    would silently stream f32 at bf16-trade accuracy).  Anything outside
    the two documented modes is rejected loudly: quietly mapping e.g.
    ``Precision.HIGH`` or a typo onto the bf16 trade would hand a caller
    ~1e-2 error where they asked for accuracy."""
    if precision in ("highest", "default"):
        return precision
    if precision == lax.Precision.HIGHEST:
        return "highest"
    if precision == lax.Precision.DEFAULT:
        return "default"
    raise ValueError(
        f"flash_attention precision must be 'highest' or 'default' "
        f"(or the matching lax.Precision), got {precision!r}"
    )


def _stream_cast(precision, *arrays):
    """The r6 bandwidth lever: ``precision="default"`` on f32 operands
    casts them to bf16 ONCE at the XLA level so the kernels stream half
    the HBM bytes (softmax statistics, accumulators, and emitted
    gradients stay f32).  Sub-f32 inputs and the "highest" mode pass
    through untouched."""
    if precision == "default":
        return tuple(
            a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a
            for a in arrays
        )
    return arrays


def _mosaic_params(interpret, pltpu):
    """Megacore partitioning hint: the (bh, major) grid axes are
    embarrassingly parallel, only the minor streaming axis is a
    sequential reduction.  Without the hint Mosaic serializes the whole
    grid on one core of a multi-core (megacore) chip."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))}


def _stream_idx(bq: int, bk: int, causal: bool, minor: str):
    """BlockSpec index map for the MINOR-axis streamed operand, with the
    causal DMA-elision clamp: masked grid steps re-target the nearest
    LIVE block, and Pallas issues no DMA when the block index repeats —
    so the causal skip saves the bytes, not just the FLOPs.  The clamp
    bounds mirror the kernels' ``live`` mask exactly (live iff
    ``kj*bk <= qi*bq + bq - 1``): ``minor="k"`` (grid (b, qi, kj)
    streaming k/v) clamps to the LAST live k block, ``minor="q"``
    (grid (b, kj, qi) streaming q/dO/lse/delta) clamps to the FIRST
    live q block.  One definition so the three call sites can never
    drift from each other or the mask."""
    if minor == "k":
        if not causal:
            return lambda b, i, j: (b, j, 0)
        return lambda b, i, j: (b, jnp.minimum(j, (i * bq + bq - 1) // bk), 0)
    assert minor == "q"
    if not causal:
        return lambda b, j, i: (b, i, 0)
    return lambda b, j, i: (b, jnp.maximum(i, (j * bk) // bq), 0)


def _blocks_for(Tq: int, Tk: int, block_q: int, block_k: int):
    """Effective (bq, bk) for EXPLICITLY-requested blocks: the largest
    divisors of the sequence lengths not exceeding the requested blocks
    (gcd) — so a 32-block request on T=48 degrades gracefully to 16-wide
    tiles.

    The degradation floor is a quarter of the smaller requested block,
    capped at 32 rows/columns: explicitly-requested tiny blocks (e.g.
    16/16 in tests) are honored, and genuinely awkward lengths (T=4104
    with a 512 request → 8-wide tiles, ~100x slower than the dense
    einsum this replaces) raise loudly rather than run silently
    degenerate.  DEFAULT-argument calls never reach this error:
    :func:`flash_attention` routes them through :func:`default_blocks`,
    which falls back to dense attention instead (r6, ADVICE r4 /
    VERDICT #7)."""
    bq = math.gcd(Tq, block_q)
    bk = math.gcd(Tk, block_k)
    floor = min(32, max(8, min(block_q, block_k) // 4))
    if bq < floor or bk < floor:
        raise ValueError(
            f"sequence lengths (Tq={Tq}, Tk={Tk}) admit only degenerate "
            f"tiles ({bq}, {bk}) for requested blocks ({block_q}, "
            f"{block_k}); use auto_block()/default args (dense fallback) "
            f"or pad the sequence"
        )
    return bq, bk


def _vma_sds(*operands):
    """ShapeDtypeStruct factory carrying the union of the operands'
    varying-axes sets — under shard_map every pallas_call output must
    declare how it varies over mesh axes (a replicated q attending
    sharded k/v still produces per-shard-varying output)."""
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    return functools.partial(jax.ShapeDtypeStruct, vma=vma)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "precision",
                     "with_lse"),
)
def _flash_forward(q, k, v, causal, block_q, block_k, interpret, precision,
                   with_lse=False):
    """Forward pass; ``with_lse=True`` also emits the per-row logsumexp
    (m + log l) as a compact [B*H, Tq, 1] f32 column — the O(T) residual
    the tiled backward reconstructs probabilities from — plus the
    STREAM-CAST q/k/v (bf16 under "default"), so the vjp saves those as
    residuals: the backward re-casts nothing and the fwd→bwd interval
    holds half the bytes."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    bq, bk = _blocks_for(Tq, Tk, block_q, block_k)
    if causal and Tq != Tk:
        raise ValueError("causal flash attention requires Tq == Tk")
    precision = _precision_str(precision)
    interpret, prec = _resolve(interpret, precision)
    out_dtype = q.dtype
    q, k, v = _stream_cast(precision, q, k, v)
    # [B, T, H, D] -> [B*H, T, D]: one grid row per (batch, head)
    q3 = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
    k3 = k.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    v3 = v.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    n_kb = Tk // bk
    kernel = functools.partial(
        _fa_kernel, scale=scale, block_q=bq, block_k=bk, n_kb=n_kb,
        causal=causal, precision=prec, with_lse=with_lse,
    )
    from jax.experimental.pallas import tpu as pltpu

    sds = _vma_sds(q3, k3, v3)
    kv_idx = _stream_idx(bq, bk, causal, "k")
    out_specs = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))
    out_shape = sds((B * H, Tq, D), out_dtype)
    if with_lse:
        out_specs = [out_specs,
                     pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))]
        out_shape = [out_shape, sds((B * H, Tq, 1), jnp.float32)]

    def make_call(interp: bool):
        return pl.pallas_call(
            kernel,
            grid=(B * H, Tq // bq, n_kb),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, bk, D), kv_idx),
                pl.BlockSpec((1, bk, D), kv_idx),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),    # running max
                pltpu.VMEM((bq, 1), jnp.float32),    # running denominator
                pltpu.VMEM((bq, D), jnp.float32),    # output accumulator
            ],
            interpret=interp,
            **_mosaic_params(interp, pltpu),
        )

    res = call_by_platform(interpret, make_call, q3, k3, v3)
    if with_lse:
        out, lse = res
        return (
            out.reshape(B, H, Tq, D).transpose(0, 2, 1, 3),
            lse,  # [B*H, Tq, 1] f32 — compact, fed to the backward as-is
            (q, k, v),  # stream-cast operands — the vjp's residuals
        )
    return res.reshape(B, H, Tq, D).transpose(0, 2, 1, 3)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "precision"),
)
def flash_attention_parts(
    q, k, v, q_pos0=0, k_pos0=0, causal=False, block_q=128, block_k=128,
    interpret=None, precision="highest",
):
    """Ring-attention inner: UNNORMALIZED flash accumulation of q against
    one K/V block with runtime global position offsets for the causal
    mask.  Returns ``(acc, m, l)`` — acc f32 [B, Tq, H, D], running max
    and denominator f32 [B, Tq, H] — which ring steps merge with the
    standard stable-softmax combine (parallel/attention.py).  Forward
    only (no custom_vjp): training uses the einsum ring path."""
    from jax.experimental.pallas import tpu as pltpu

    interpret, prec = _resolve(interpret, precision)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    if Tq % bq or Tk % bk:
        raise ValueError(
            f"sequence lengths (Tq={Tq}, Tk={Tk}) must be multiples of the "
            f"blocks (bq={bq}, bk={bk})"
        )
    q3 = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
    k3 = k.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    v3 = v.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    n_kb = Tk // bk
    kernel = functools.partial(
        _fa_kernel, scale=scale, block_q=bq, block_k=bk, n_kb=n_kb,
        causal=causal, precision=prec, parts=True,
    )
    scalar_spec = pl.BlockSpec((1, 1), lambda b, i, j: (0, 0),
                               memory_space=pltpu.SMEM)
    tile_q = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))
    tile_k = pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0))
    tile_ml = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
    sds = _vma_sds(q3, k3, v3)

    def make_call(interp: bool):
        return pl.pallas_call(
            kernel,
            grid=(B * H, Tq // bq, n_kb),
            in_specs=[scalar_spec, scalar_spec, tile_q, tile_k, tile_k],
            out_specs=[tile_q, tile_ml, tile_ml],
            out_shape=[
                sds((B * H, Tq, D), jnp.float32),
                sds((B * H, Tq, 1), jnp.float32),
                sds((B * H, Tq, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, D), jnp.float32),
            ],
            interpret=interp,
            **_mosaic_params(interp, pltpu),
        )

    acc, m, l = call_by_platform(
        interpret, make_call,
        jnp.asarray(q_pos0, jnp.int32).reshape(1, 1),
        jnp.asarray(k_pos0, jnp.int32).reshape(1, 1),
        q3, k3, v3,
    )
    acc = acc.reshape(B, H, Tq, D).transpose(0, 2, 1, 3)
    m = m[..., 0].reshape(B, H, Tq).transpose(0, 2, 1)
    l = l[..., 0].reshape(B, H, Tq).transpose(0, 2, 1)
    return acc, m, l


def _fa_bwd_dq_kernel(*refs, scale, block_q, block_k, n_kb, causal, precision,
                      parts=False):
    """Backward dq: grid (bh, q-block, k-block minor).  Recomputes each
    score block from q/k and the saved logsumexp, accumulates
    dq += ds · K in VMEM scratch across the k steps.  Contractions run in
    the operands' dtype (f32 accumulate); ds absorbs the softmax scale so
    the accumulated dq needs no finish-time rescale.

    ``parts=True`` prepends two SMEM scalars (global position offsets of
    this chip's Q and the in-flight K/V block) shifting the causal mask —
    the ring backward's analogue of the parts forward kernel."""
    if parts:
        q_off_ref, k_off_ref = refs[0], refs[1]
        q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dq_ref = refs[2:9]
        (dq_scr,) = refs[9:]
        q_pos0 = q_off_ref[0, 0]
        k_pos0 = k_off_ref[0, 0]
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dq_ref = refs[:7]
        (dq_scr,) = refs[7:]
        q_pos0 = k_pos0 = 0
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live = (
        (k_pos0 + kj * block_k <= q_pos0 + qi * block_q + block_q - 1)
        if causal
        else True
    )

    @pl.when(live)
    def _step():
        q = q_ref[0]                                   # (bq, D)
        kb = k_ref[0]                                  # (bk, D)
        vb = v_ref[0]
        do = do_ref[0]                                 # (bq, D)
        lse = lse_ref[0][:, 0]                         # (bq,)
        dlt = dlt_ref[0][:, 0]                         # (bq,)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        ) * scale
        if causal:
            q_pos = q_pos0 + qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_pos0 + kj * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG)
        p = jnp.exp(s - lse[:, None])                  # (bq, bk)
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        ds = p * (dp - dlt[:, None]) * scale
        if precision == lax.Precision.HIGHEST:
            ds2, kb2 = ds, kb.astype(jnp.float32)
        else:
            ds2, kb2 = ds.astype(kb.dtype), kb
        dot = jax.lax.dot_general(
            ds2, kb2, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        if n_kb == 1 and not parts:
            # single (always-live) k step: direct store, no zeros
            # read-modify-write — value-identical to 0 + dot
            dq_scr[...] = dot
        else:
            dq_scr[...] = dq_scr[...] + dot

    @pl.when(kj == n_kb - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)   # scale folded in ds


def _fa_bwd_dkv_kernel(*refs, scale, block_q, block_k, n_qb, causal,
                       precision, parts=False):
    """Backward dk/dv: grid (bh, k-block, q-block minor).  Accumulates
    dv += pᵀ · dO and dk += dsᵀ · q in VMEM scratch across the q steps
    (operand-dtype contractions, f32 accumulate; ds absorbs the scale).

    ``parts=True``: SMEM global position offsets, as in the dq kernel."""
    if parts:
        q_off_ref, k_off_ref = refs[0], refs[1]
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dk_ref,
         dv_ref) = refs[2:10]
        dk_scr, dv_scr = refs[10:]
        q_pos0 = q_off_ref[0, 0]
        k_pos0 = k_off_ref[0, 0]
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dk_ref,
         dv_ref) = refs[:8]
        dk_scr, dv_scr = refs[8:]
        q_pos0 = k_pos0 = 0
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    live = (
        (k_pos0 + kj * block_k <= q_pos0 + qi * block_q + block_q - 1)
        if causal
        else True
    )

    @pl.when(live)
    def _step():
        q = q_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0]
        dlt = dlt_ref[0][:, 0]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        ) * scale
        if causal:
            q_pos = q_pos0 + qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_pos0 + kj * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG)
        p = jnp.exp(s - lse[:, None])                  # (bq, bk)
        if precision == lax.Precision.HIGHEST:
            p2, do2 = p, do.astype(jnp.float32)
        else:
            p2, do2 = p.astype(do.dtype), do
        # single q step AND every step live (non-causal non-parts only:
        # a causal single-q grid can dead-step high k blocks, which
        # must then finish from the _init zeros): direct store instead
        # of the zeros read-modify-write — value-identical to 0 + dot
        direct = n_qb == 1 and not parts and not causal
        dv_dot = jax.lax.dot_general(
            p2, do2, (((0,), (0,)), ((), ())),         # pᵀ·do
            preferred_element_type=jnp.float32, precision=precision,
        )
        dv_scr[...] = dv_dot if direct else dv_scr[...] + dv_dot
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        ds = p * (dp - dlt[:, None]) * scale
        if precision == lax.Precision.HIGHEST:
            ds2, q2 = ds, q.astype(jnp.float32)
        else:
            ds2, q2 = ds.astype(q.dtype), q
        dk_dot = jax.lax.dot_general(
            ds2, q2, (((0,), (0,)), ((), ())),         # dsᵀ · q -> (bk, D)
            preferred_element_type=jnp.float32, precision=precision,
        )
        dk_scr[...] = dk_dot if direct else dk_scr[...] + dk_dot

    @pl.when(qi == n_qb - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)   # scale folded in ds
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "precision",
                     "grad_dtypes"),
)
def _flash_backward(q, k, v, out, lse3, do, causal, block_q, block_k,
                    interpret, precision, grad_dtypes=None):
    """Tiled flash backward: dq in one pallas_call (k minor), dk/dv in a
    second (q minor).  ``lse3`` arrives AND is consumed in compact
    [B*H, Tq, 1] layout (the residual held across the fwd→bwd interval
    and the bytes the kernels stream are both O(T), not O(128·T) — r4
    advisor note + r6 MFU fix); delta = rowsum(dO ∘ O) is a cheap XLA
    reduction emitted in the same compact column.  Under
    ``precision="default"`` the streamed operands (q/k/v/dO) are bf16;
    gradients are emitted in ``grad_dtypes`` — the PRIMAL (pre-cast)
    dtypes per operand, defaulting to the cotangent dtype — so each
    cotangent matches its primal even for mixed-dtype q/k/v."""
    from jax.experimental.pallas import tpu as pltpu

    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    bq, bk = _blocks_for(Tq, Tk, block_q, block_k)
    scale = 1.0 / math.sqrt(D)
    precision = _precision_str(precision)
    interpret, prec = _resolve(interpret, precision)
    # delta_i = sum_d dO_id * O_id in f32, BEFORE the bandwidth cast
    delta = jnp.einsum(
        "bqhd,bqhd->bhq", do.astype(jnp.float32), out.astype(jnp.float32)
    ).reshape(B * H, Tq)
    dlt3 = delta[..., None]                       # [B*H, Tq, 1]
    dq_dtype, dk_dtype, dv_dtype = grad_dtypes or (do.dtype,) * 3
    q, k, v, do = _stream_cast(precision, q, k, v, do)
    q3 = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
    k3 = k.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    v3 = v.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    do3 = do.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
    sds = _vma_sds(q3, k3, v3, do3)
    n_qb, n_kb = Tq // bq, Tk // bk
    tile_q = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))
    tile_ml = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
    tile_k_minor = pl.BlockSpec((1, bk, D), _stream_idx(bq, bk, causal, "k"))

    def make_dq(interp: bool):
        return pl.pallas_call(
            functools.partial(
                _fa_bwd_dq_kernel, scale=scale, block_q=bq, block_k=bk,
                n_kb=n_kb, causal=causal, precision=prec,
            ),
            grid=(B * H, n_qb, n_kb),
            in_specs=[tile_q, tile_k_minor, tile_k_minor, tile_q, tile_ml,
                      tile_ml],
            out_specs=tile_q,
            out_shape=sds((B * H, Tq, D), dq_dtype),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
            interpret=interp,
            **_mosaic_params(interp, pltpu),
        )

    operands = (q3, k3, v3, do3, lse3, dlt3)
    dq = call_by_platform(interpret, make_dq, *operands)
    # dk/dv: k-block is the 2nd grid axis, q streams as the minor axis
    q_idx = _stream_idx(bq, bk, causal, "q")
    tile_q_minor = pl.BlockSpec((1, bq, D), q_idx)
    tile_ml_minor = pl.BlockSpec((1, bq, 1), q_idx)
    tile_k = pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0))

    def make_dkv(interp: bool):
        return pl.pallas_call(
            functools.partial(
                _fa_bwd_dkv_kernel, scale=scale, block_q=bq, block_k=bk,
                n_qb=n_qb, causal=causal, precision=prec,
            ),
            grid=(B * H, n_kb, n_qb),
            in_specs=[tile_q_minor, tile_k, tile_k, tile_q_minor,
                      tile_ml_minor, tile_ml_minor],
            out_specs=[tile_k, tile_k],
            out_shape=[
                sds((B * H, Tk, D), dk_dtype),
                sds((B * H, Tk, D), dv_dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, D), jnp.float32),
                pltpu.VMEM((bk, D), jnp.float32),
            ],
            interpret=interp,
            **_mosaic_params(interp, pltpu),
        )

    dk, dv = call_by_platform(interpret, make_dkv, *operands)
    reshape = lambda a, T: a.reshape(B, H, T, D).transpose(0, 2, 1, 3)
    return reshape(dq, Tq), reshape(dk, Tk), reshape(dv, Tk)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "precision"),
)
def flash_attention_bwd_parts(
    q, k, v, do, lse, delta, q_pos0=0, k_pos0=0, causal=False,
    block_q=128, block_k=128, interpret=None, precision="highest",
):
    """Ring-attention inner BACKWARD: gradients of one chip's queries
    against one in-flight K/V block, with runtime global position offsets
    for the causal mask — the bwd analogue of
    :func:`flash_attention_parts` (same tiled kernels as the single-chip
    backward, SMEM offsets added).

    ``lse`` and ``delta`` are per-row [B, Tq, H] f32: the ring-global
    logsumexp (m + log l merged across ALL ring steps) and
    rowsum(dO ∘ O); the kernels consume them as compact [B*H, Tq, 1]
    columns.  Returns ``(dq_partial, dk_block, dv_block)`` in **f32**
    regardless of input dtype — the caller accumulates partials across
    ring steps, and rounding each partial to a low-precision input dtype
    would add n independent roundings the single-chip backward doesn't
    have (it rounds once from f32 scratch).  The caller sums dq over ring
    steps and rotates dk/dv accumulators with their blocks
    (parallel/attention.py:_raf_bwd)."""
    from jax.experimental.pallas import tpu as pltpu

    interpret, prec = _resolve(interpret, precision)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    if Tq % bq or Tk % bk:
        raise ValueError(
            f"sequence lengths (Tq={Tq}, Tk={Tk}) must be multiples of the "
            f"blocks (bq={bq}, bk={bk})"
        )
    q3 = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
    k3 = k.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    v3 = v.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    do3 = do.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
    to_col = lambda a: a.astype(jnp.float32).transpose(0, 2, 1).reshape(
        B * H, Tq, 1)
    lse3 = to_col(lse)
    dlt3 = to_col(delta)
    offs = (
        jnp.asarray(q_pos0, jnp.int32).reshape(1, 1),
        jnp.asarray(k_pos0, jnp.int32).reshape(1, 1),
    )
    sds = _vma_sds(q3, k3, v3, do3)
    n_qb, n_kb = Tq // bq, Tk // bk
    scalar_spec = pl.BlockSpec((1, 1), lambda b, i, j: (0, 0),
                               memory_space=pltpu.SMEM)
    tile_q = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))
    tile_ml = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
    tile_k_minor = pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0))

    def make_dq(interp: bool):
        return pl.pallas_call(
            functools.partial(
                _fa_bwd_dq_kernel, scale=scale, block_q=bq, block_k=bk,
                n_kb=n_kb, causal=causal, precision=prec, parts=True,
            ),
            grid=(B * H, n_qb, n_kb),
            in_specs=[scalar_spec, scalar_spec, tile_q, tile_k_minor,
                      tile_k_minor, tile_q, tile_ml, tile_ml],
            out_specs=tile_q,
            out_shape=sds((B * H, Tq, D), jnp.float32),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
            interpret=interp,
            **_mosaic_params(interp, pltpu),
        )

    operands = (*offs, q3, k3, v3, do3, lse3, dlt3)
    dq = call_by_platform(interpret, make_dq, *operands)
    tile_q_minor = pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0))
    tile_ml_minor = pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0))
    tile_k = pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0))
    scalar_spec_m = pl.BlockSpec((1, 1), lambda b, j, i: (0, 0),
                                 memory_space=pltpu.SMEM)

    def make_dkv(interp: bool):
        return pl.pallas_call(
            functools.partial(
                _fa_bwd_dkv_kernel, scale=scale, block_q=bq, block_k=bk,
                n_qb=n_qb, causal=causal, precision=prec, parts=True,
            ),
            grid=(B * H, n_kb, n_qb),
            in_specs=[scalar_spec_m, scalar_spec_m, tile_q_minor, tile_k,
                      tile_k, tile_q_minor, tile_ml_minor, tile_ml_minor],
            out_specs=[tile_k, tile_k],
            out_shape=[
                sds((B * H, Tk, D), jnp.float32),
                sds((B * H, Tk, D), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, D), jnp.float32),
                pltpu.VMEM((bk, D), jnp.float32),
            ],
            interpret=interp,
            **_mosaic_params(interp, pltpu),
        )

    dk, dv = call_by_platform(interpret, make_dkv, *operands)
    reshape = lambda a, T: a.reshape(B, H, T, D).transpose(0, 2, 1, 3)
    return reshape(dq, Tq), reshape(dk, Tk), reshape(dv, Tk)


def _dense_attention(q, k, v, causal, precision):
    """Dense XLA attention — the documented fallback for
    default-argument calls whose sequence lengths admit only sub-MXU
    tiles (:func:`default_blocks` → None).  Delegates to the ONE
    reference implementation (lazy import — parallel.attention imports
    this module lazily too, so there is no cycle), passing the caller's
    precision trade through.  Differentiable via plain autodiff."""
    from ..parallel.attention import attention_reference

    _, prec = _resolve(False, precision)
    return attention_reference(q, k, v, causal=causal, precision=prec)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_tiled(q, k, v, causal, block_q, block_k, interpret,
                           precision):
    interpret, _ = _resolve(interpret, precision)
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret,
                          precision)


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret, precision):
    interpret, _ = _resolve(interpret, precision)
    out, lse3, (qs, ks, vs) = _flash_forward(
        q, k, v, causal, block_q, block_k, interpret, precision,
        with_lse=True
    )
    # the kernel emits the logsumexp as a compact [B*H, Tq, 1] f32 column
    # (true O(T)) — the residual saved across the whole forward→backward
    # interval AND the operand layout the backward kernels stream.  The
    # SAVED q/k/v are the stream-cast versions (bf16 under "default"):
    # half the residual bytes, and the backward re-casts nothing.  Two
    # zero-size carriers preserve k/v's PRIMAL dtypes so each cotangent
    # can match its primal even for mixed-dtype operands (q's rides on
    # the cotangent itself: out keeps q's dtype).
    return out, (qs, ks, vs, out, lse3,
                 jnp.zeros((0,), k.dtype), jnp.zeros((0,), v.dtype))


def _fa_bwd(causal, block_q, block_k, interpret, precision, res, do):
    q, k, v, out, lse3, zk, zv = res
    # honor the caller's precision trade in the backward too — it is the
    # dominant training cost, so "default" (bf16 streams + bf16 MXU
    # passes) must actually apply here, not just in the forward kernel
    interpret, _ = _resolve(interpret, precision)
    return _flash_backward(
        q, k, v, out, lse3, do, causal, block_q, block_k, interpret,
        precision, grad_dtypes=(do.dtype, zk.dtype, zv.dtype)
    )


_flash_attention_tiled.defvjp(_fa_fwd, _fa_bwd)


def flash_attention(q, k, v, causal=False, block_q=None, block_k=None,
                    interpret=None, precision="highest"):
    """Tiled flash attention on TPU (Pallas), fwd AND bwd kernels.

    Shapes match :func:`parallel.attention.attention_reference`:
    q [B, Tq, H, D], k/v [B, Tk, H, D] → [B, Tq, H, D].
    ``interpret=None`` lowers per dispatch platform (Mosaic on a TPU,
    the Pallas interpreter elsewhere — ops/platform.py).
    ``precision``: "highest" (true-f32 MXU passes, matches the dense
    reference bit-for-bit-ish) or "default" (bf16 end-to-end: f32 inputs
    are cast to bf16 once at the XLA level, the kernels stream and
    contract bf16 with f32 accumulators — the usual flash-attention
    trade, ~1e-2 relative on f32 inputs, ~2x the MFU).

    Default-argument blocks come from the MEASURED block autotuner
    (``core/blocktuner.TUNER``): warm starts from the kernel-profile
    store, measured walls take over as they arrive, and the static
    :func:`default_blocks` pair — the r5-sweep 512/512 sweet spot
    degraded by gcd — remains the cold-start fallback.  The tuner and
    the static policy agree on WHEN tiling is legal (both gate on a
    >= 128 divisor), so the DENSE-attention fallback for awkward
    sequence lengths (e.g. T=96, T=4104 — sub-MXU tiles are slower than
    the dense einsum they replace; ADVICE r4 / VERDICT #7) is unchanged.
    Explicitly-passed blocks BYPASS tuning entirely and keep the strict
    contract: degrade by gcd to the :func:`_blocks_for` floor, then
    raise.  Training memory is O(T) residuals (out + per-row logsumexp,
    both compact) + O(block²) tiles — no [T, T] materialization in
    either direction."""
    precision = _precision_str(precision)
    if block_q is None and block_k is None:
        blocks = _tuned_blocks(q.shape, k.shape, precision)
        if blocks is None:
            return _dense_attention(q, k, v, causal, precision)
        block_q, block_k = blocks
    elif block_q is None or block_k is None:
        block_q = block_q or block_k
        block_k = block_k or block_q
    return _flash_attention_tiled(
        q, k, v, causal, block_q, block_k, interpret, precision
    )


def _tuned_blocks(q_shape, k_shape,
                  precision: str) -> tuple[int, int] | None:
    """Default-argument block choice: ask the measured autotuner, with
    the static :func:`default_blocks` pair as its cold-start fallback
    (and as the answer outright if the tuner is unavailable — the flash
    path must never fail because telemetry plumbing did).  None means
    "no legal tile, run dense" — the tuner's empty-grid condition and
    ``default_blocks``' None are the same predicate by construction."""
    Tq, Tk = int(q_shape[1]), int(k_shape[1])
    fallback = default_blocks(Tq, Tk)
    try:
        from ..core.blocktuner import TUNER

        sig = ("flash_attention.highest" if precision == "highest"
               else "flash_attention.bf16_default")
        choice = TUNER.choose(sig, Tq, Tk, shape=tuple(q_shape),
                              fallback=fallback)
    except Exception:  # noqa: BLE001 - tuner trouble must not sink math
        return fallback
    return choice if choice is not None else fallback


def fused_qkv(x, wq, wk, wv, precision=None):
    """The three attention input projections as ONE concatenated GEMM:
    ``x @ [wq | wk | wv]`` split back into (q, k, v).

    One MXU pass over x instead of three (one x read from HBM, one
    weight stream, 3x the N dimension per launch — the kernel-level MFU
    lever for the projection stage), and BIT-IDENTICAL to the three
    separate matmuls: every output column is an independent dot product
    over the same contraction order, so concatenating columns changes
    which results land where, never what any result is.

    ``x`` is [..., E]; each ``w*`` is [E, F*] (the F's may differ, e.g.
    grouped-query K/V heads).  Returns views of one buffer — slice
    copies only materialize if a consumer forces them."""
    w = jnp.concatenate([wq, wk, wv], axis=-1)
    qkv = jnp.matmul(x, w, precision=precision)
    fq, fk = wq.shape[-1], wk.shape[-1]
    return (qkv[..., :fq], qkv[..., fq:fq + fk], qkv[..., fq + fk:])


def fused_qkv_attention(x, wq, wk, wv, num_heads, causal=False,
                        interpret=None, precision="highest"):
    """Fused projection + tuned flash attention: ``x`` [B, T, E] through
    :func:`fused_qkv` (one GEMM), heads split to [B, T, H, D], then the
    DEFAULT-argument :func:`flash_attention` path — i.e. the block
    autotuner picks the tile geometry.  The fused-GEMM and one-shot-
    softmax variants this module grew are both on this path: the first
    unconditionally, the second whenever the tuner engages
    ``block_k == Tk``."""
    B, T, _ = x.shape
    q, k, v = fused_qkv(x, wq, wk, wv)
    q = q.reshape(B, T, num_heads, -1)
    k = k.reshape(B, T, num_heads, -1)
    v = v.reshape(B, T, num_heads, -1)
    return flash_attention(q, k, v, causal=causal, interpret=interpret,
                           precision=precision)
