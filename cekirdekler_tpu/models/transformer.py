"""Flagship model family: a decoder-only transformer, TPU-first.

The reference framework predates ML models (its "models" are demo kernels,
SURVEY.md §2.1 #17/#20); this family exists because a complete TPU compute
framework must demonstrate the parallel tier end-to-end — dp/fsdp/tp/sp
shardings, ring/Ulysses long-context attention (parallel/attention.py),
remat, and a full jittable train step over a mesh.

Design choices (TPU-first, SURVEY.md §7 design stance):
- Params are plain pytrees (dicts) with a parallel pytree of
  ``PartitionSpec`` — GSPMD places every matmul; no manual collectives in
  the dense path.
- Compute in bfloat16 (MXU-native), params + optimizer state in float32.
- ``jax.checkpoint`` on each block when ``remat=True`` — recompute
  activations in backward, trading FLOPs for HBM.
- Static shapes; layers scanned-free (unrolled python loop — layer count
  is static) so XLA sees one big fusable graph.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.attention import attention_reference, ring_attention, ulysses_attention
from ..parallel.mesh import constrain, shard_map

__all__ = ["TransformerConfig", "Transformer", "cross_entropy_loss"]


@dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    d_ff: int = 2048
    max_seq: int = 2048
    dtype: Any = jnp.bfloat16          # activation/compute dtype (MXU-native)
    param_dtype: Any = jnp.float32
    attention: str = "dense"            # "dense" | "flash" | "ring" | "ulysses"
    # flash kernel precision; None = follow dtype (sub-f32 activations ->
    # "default" bf16 streaming, f32 -> "highest" true-f32 passes)
    attention_precision: str | None = None
    remat: bool = False
    sp_axis: str = "sp"
    # mixture of experts: n_experts > 0 turns every ``moe_every``-th block's
    # FFN into a top-1 routed expert layer (experts shard over ep).
    # moe_capacity_factor > 0 selects Switch-style capacity dispatch
    # (per-chip FFN flops ~ cap/E of compute-all; over-capacity tokens
    # drop); 0 keeps the dense compute-all formulation (exact)
    n_experts: int = 0
    moe_every: int = 2
    moe_capacity_factor: float = 0.0
    # pipeline parallelism: pp_stages > 1 stacks the blocks and runs them
    # GPipe-style over the pp axis with n_microbatches per step
    pp_stages: int = 1
    n_microbatches: int = 1

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def is_moe_block(self, i: int) -> bool:
        if self.n_experts <= 0:
            return False
        if self.pp_stages > 1:
            return True  # pp needs homogeneous (stackable) blocks
        return (i + 1) % self.moe_every == 0


def _init(rng, shape, scale, dtype):
    return (jax.random.normal(rng, shape) * scale).astype(dtype)


class Transformer:
    """Decoder-only transformer with mesh-aware sharding specs."""

    def __init__(self, config: TransformerConfig):
        self.config = config

    # -- parameters ----------------------------------------------------------
    def init(self, rng) -> dict:
        c = self.config
        keys = jax.random.split(rng, 2 + c.n_layers)
        params: dict = {
            "embed": _init(keys[0], (c.vocab, c.d_model), 0.02, c.param_dtype),
            "final_norm": jnp.ones((c.d_model,), c.param_dtype),
            "blocks": [],
        }
        for i in range(c.n_layers):
            ks = jax.random.split(keys[2 + i], 5)
            d, h, f = c.d_model, c.n_heads * c.head_dim, c.d_ff
            block = {
                "ln1": jnp.ones((d,), c.param_dtype),
                "wqkv": _init(ks[0], (d, 3 * h), d**-0.5, c.param_dtype),
                "wo": _init(ks[1], (h, d), h**-0.5, c.param_dtype),
                "ln2": jnp.ones((d,), c.param_dtype),
            }
            if c.is_moe_block(i):
                block["router"] = _init(ks[4], (d, c.n_experts), 0.02, c.param_dtype)
                block["w1"] = _init(ks[2], (c.n_experts, d, f), d**-0.5, c.param_dtype)
                block["w2"] = _init(ks[3], (c.n_experts, f, d), f**-0.5, c.param_dtype)
            else:
                block["w1"] = _init(ks[2], (d, f), d**-0.5, c.param_dtype)
                block["w2"] = _init(ks[3], (f, d), f**-0.5, c.param_dtype)
            params["blocks"].append(block)
        if c.pp_stages > 1:
            from ..parallel.pipeline_parallel import stack_layers

            if c.n_layers % c.pp_stages != 0:
                raise ValueError(
                    f"n_layers ({c.n_layers}) must divide into pp_stages ({c.pp_stages})"
                )
            params["blocks"] = stack_layers(params["blocks"])
        return params

    def param_specs(self) -> dict:
        """PartitionSpec pytree matching :meth:`init` — tp shards the head
        and ff dimensions, fsdp shards the other matmul dimension."""
        c = self.config

        def block_spec(i: int) -> dict:
            spec = {
                "ln1": P(),
                "wqkv": P("fsdp", "tp"),
                "wo": P("tp", "fsdp"),
                "ln2": P(),
            }
            if c.is_moe_block(i):
                spec["router"] = P()
                spec["w1"] = P("ep", "fsdp", "tp")
                spec["w2"] = P("ep", "tp", "fsdp")
            else:
                spec["w1"] = P("fsdp", "tp")
                spec["w2"] = P("tp", "fsdp")
            return spec

        blocks = [block_spec(i) for i in range(c.n_layers)]
        if c.pp_stages > 1:
            # stacked layer dim shards over pp (each stage holds its layers)
            blocks = jax.tree_util.tree_map(
                lambda s: P("pp", *s), blocks[0],
                is_leaf=lambda x: isinstance(x, P),
            )
        return {
            "embed": P("tp", "fsdp"),
            "final_norm": P(),
            "blocks": blocks,
        }

    def shard_params(self, params: dict, mesh: Mesh) -> dict:
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params,
            self.param_specs(),
        )

    # -- forward -------------------------------------------------------------
    def _embed_lookup(self, embed, tokens, mesh: Mesh | None):
        """Token → embedding row.  Under a mesh the table is sharded
        ``P("tp", "fsdp")`` (vocab over tp), so a plain gather forces GSPMD
        to rematerialize the full table every step; the one-hot matmul form
        is a contraction over the sharded vocab dim instead — XLA keeps the
        shards in place and inserts one psum over tp (MXU-friendly)."""
        c = self.config
        if mesh is None or mesh.shape.get("tp", 1) <= 1:
            # vocab dim unsharded: the gather is local and cheap — the
            # one-hot contraction would cost O(B·T·vocab·D) for nothing
            return embed.astype(c.dtype)[tokens]
        onehot = jax.nn.one_hot(tokens, c.vocab, dtype=c.dtype)
        onehot = constrain(onehot, mesh, ("dp", "fsdp"), c.sp_axis, "tp")
        return onehot @ embed.astype(c.dtype)

    def _attention(self, q, k, v, mesh: Mesh | None):
        c = self.config
        if c.attention in ("ring", "ulysses") and mesh is not None:
            # sequence-parallel paths run under shard_map: batch over the
            # data axes, sequence over sp, heads over tp; the ring/all-to-all
            # collectives ride the sp axis only
            inner = ring_attention if c.attention == "ring" else ulysses_attention
            spec = P(("dp", "fsdp"), c.sp_axis, "tp", None)
            fn = shard_map(
                partial(inner, axis=c.sp_axis, causal=True),
                mesh=mesh,
                in_specs=(spec,) * 3,
                out_specs=spec,
            )
            return fn(q, k, v)
        if c.attention == "flash":
            # Pallas hot op (ops/flash_attention.py): tiled stable-softmax,
            # O(block²) attention memory, fwd+bwd kernels, differentiable.
            from ..ops.flash_attention import default_blocks, flash_attention

            # precision follows the activation dtype (overridable via
            # config): sub-f32 activations (the bf16 config default) take
            # the r6 "default" path — bf16 streamed through every fwd+bwd
            # contraction with f32 accumulators, single-pass MXU; f32
            # activations keep "highest" (true-f32 passes, the r5 ~5e-5
            # dense agreement the f32 tests pin)
            prec = c.attention_precision or (
                "default" if jnp.dtype(c.dtype).itemsize < 4 else "highest"
            )
            # measured 512/512 sweet spot, degraded by gcd; None = only
            # sub-128 (sub-MXU) tiles divide T -> dense is faster (the
            # documented default-args convention, ADVICE r4 / VERDICT #7)
            blocks = default_blocks(q.shape[1])
            bq, bk = blocks if blocks is not None else (None, None)
            if bq is not None and mesh is None:
                return flash_attention(q, k, v, True, bq, bk, None, prec)
            if bq is not None and mesh is not None and (
                q.shape[0] % (mesh.shape.get("dp", 1)
                              * mesh.shape.get("fsdp", 1)) == 0
                and c.n_heads % mesh.shape.get("tp", 1) == 0
                and mesh.shape.get(c.sp_axis, 1) <= 1
            ):
                # batch-sharded mesh (dp/fsdp; heads optionally over tp):
                # causal self-attention is independent per (batch, head),
                # so each shard runs the SAME Pallas kernel on its local
                # slice under shard_map — pallas_call cannot be
                # auto-partitioned by GSPMD, but it doesn't need to be
                # when no sharded axis crosses the attention reduction.
                # Sequence-sharded meshes use ring/ulysses instead.
                # interpret follows the MESH's devices, not the process
                # default backend — on a host whose default device is a
                # TPU, a CPU-rig mesh must still get the interpreter.
                interp = mesh.devices.flat[0].platform != "tpu"
                spec = P(("dp", "fsdp"), None, "tp", None)
                # the Pallas INTERPRETER can't satisfy the replication/
                # vma checker — relax it off-TPU only, same workaround
                # as the ring/ulysses sharded wrappers
                kw = {"check_vma": False} if interp else {}
                fn = shard_map(
                    lambda qq, kk, vv: flash_attention(
                        qq, kk, vv, True, bq, bk, interp, prec),
                    mesh=mesh,
                    in_specs=(spec,) * 3,
                    out_specs=spec,
                    **kw,
                )
                return fn(q, k, v)
            # degenerate tiling, uneven batch/head sharding, or a
            # sequence-sharded mesh: the GSPMD dense path handles all of
            # them (it tolerates uneven sharding via padding) — still
            # honoring the derived precision trade (a bf16 model's dense
            # fallback must not silently pay multi-pass-f32 einsums)
            return attention_reference(
                q, k, v, causal=True,
                precision=(jax.lax.Precision.DEFAULT
                           if prec == "default" else None),
            )
        return attention_reference(q, k, v, causal=True)

    def _block(self, params: dict, x, mesh: Mesh | None):
        """Pre-norm block: x + Attn(LN(x)); x + FFN(LN(x)) (dense or MoE)."""
        c = self.config
        B, T, _ = x.shape
        h = _rms_norm(x, params["ln1"])
        qkv = h @ params["wqkv"].astype(c.dtype)
        if mesh is not None:
            qkv = constrain(qkv, mesh, ("dp", "fsdp"), c.sp_axis, "tp")
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shp = (B, T, c.n_heads, c.head_dim)
        o = self._attention(q.reshape(shp), k.reshape(shp), v.reshape(shp), mesh)
        o = o.reshape(B, T, -1) @ params["wo"].astype(c.dtype)
        if mesh is not None:
            o = constrain(o, mesh, ("dp", "fsdp"), c.sp_axis, None)
        x = x + o
        h = _rms_norm(x, params["ln2"])
        if "router" in params:
            from .moe import moe_ffn, moe_ffn_capacity, moe_ffn_sharded

            cf = c.moe_capacity_factor
            if mesh is not None:
                h = moe_ffn_sharded(mesh, h, params["router"], params["w1"],
                                    params["w2"], capacity_factor=cf)
            elif cf > 0:
                h = moe_ffn_capacity(h, params["router"], params["w1"],
                                     params["w2"], capacity_factor=cf)
            else:
                # under pp (or single device) GSPMD auto-shards the expert
                # dim from the param shardings
                h = moe_ffn(h, params["router"], params["w1"], params["w2"])
            return x + h
        h = jax.nn.gelu(h @ params["w1"].astype(c.dtype))
        if mesh is not None:
            h = constrain(h, mesh, ("dp", "fsdp"), c.sp_axis, "tp")
        h = h @ params["w2"].astype(c.dtype)
        return x + h

    def apply(self, params: dict, tokens, mesh: Mesh | None = None):
        """tokens [B, T] int32 → logits [B, T, vocab] (f32)."""
        c = self.config
        x = self._embed_lookup(params["embed"], tokens, mesh)
        if mesh is not None:
            x = constrain(x, mesh, ("dp", "fsdp"), c.sp_axis, None)
        if c.pp_stages > 1:
            # blocks is a stacked pytree (init, pp_stages>1 branch) — run it
            # through the GPipe microbatch pipeline over the pp axis
            x = self._apply_pipelined(params["blocks"], x, mesh)
        else:
            def block(bp, x):
                return self._block(bp, x, mesh)

            if c.remat:
                block = jax.checkpoint(block)
            for bp in params["blocks"]:
                x = block(bp, x)
        x = _rms_norm(x, params["final_norm"])
        logits = x.astype(jnp.float32) @ params["embed"].astype(jnp.float32).T
        if mesh is not None:
            logits = constrain(logits, mesh, ("dp", "fsdp"), c.sp_axis, "tp")
        return logits

    def _apply_pipelined(self, stacked_blocks, x, mesh: Mesh | None):
        """GPipe over the pp axis: each stage holds n_layers/pp stacked
        layers; activations rotate around the ring per microbatch step
        (parallel/pipeline_parallel.py).  Inside the stage the other mesh
        axes stay in GSPMD auto mode, so blocks run with mesh=None."""
        from ..parallel.pipeline_parallel import gpipe

        c = self.config

        def stage_fn(local_blocks, x_mb):
            n_local = jax.tree_util.tree_leaves(local_blocks)[0].shape[0]

            def one(bp_i, x_mb):
                return self._block(bp_i, x_mb, None)

            if c.remat:
                one = jax.checkpoint(one)
            for i in range(n_local):
                bp_i = jax.tree_util.tree_map(lambda a: a[i], local_blocks)
                x_mb = one(bp_i, x_mb)
            return x_mb

        if mesh is None:
            # no mesh: run the stack sequentially (pp degenerates)
            n = jax.tree_util.tree_leaves(stacked_blocks)[0].shape[0]
            for i in range(n):
                bp_i = jax.tree_util.tree_map(lambda a: a[i], stacked_blocks)
                x = self._block(bp_i, x, None)
            return x
        return gpipe(stage_fn, stacked_blocks, x, c.n_microbatches, mesh)

    # -- training ------------------------------------------------------------
    def loss_fn(self, params: dict, batch: dict, mesh: Mesh | None = None):
        """Next-token cross entropy; batch = {"tokens": [B, T+1]}."""
        tokens = batch["tokens"]
        logits = self.apply(params, tokens[:, :-1], mesh)
        return cross_entropy_loss(logits, tokens[:, 1:])

    def make_train_step(self, optimizer, mesh: Mesh | None = None) -> Callable:
        """Build the full jittable train step: loss, grads, optax update.

        Returns ``step(params, opt_state, batch) -> (params, opt_state,
        loss)``; caller jits (optionally with shardings over ``mesh``).
        """

        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(
                lambda p, b: self.loss_fn(p, b, mesh)
            )(params, batch)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
            return params, opt_state, loss

        return step


def _rms_norm(x, gain):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
    return (y * gain.astype(jnp.float32)).astype(dt)


def cross_entropy_loss(logits, labels):
    """Mean next-token cross entropy (f32)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -ll.mean()
