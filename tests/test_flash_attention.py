"""Flash attention (ops/flash_attention.py) vs the dense reference: values
and gradients must agree; causal masking and uneven Tq/Tk supported.
Runs in Pallas interpret mode on the rig; compiled on the TPU by
``chip_smoke.py``'s kernel stage."""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402

from cekirdekler_tpu.ops.flash_attention import flash_attention  # noqa: E402
from cekirdekler_tpu.parallel.attention import attention_reference  # noqa: E402


def _qkv(B=2, Tq=64, Tk=64, H=2, D=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda t: jnp.asarray(rng.standard_normal((B, t, H, D)), jnp.float32)
    return mk(Tq), mk(Tk), mk(Tk)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    want = attention_reference(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal, 32, 32, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_cross_attention_tq_ne_tk():
    q, k, v = _qkv(Tq=32, Tk=96)
    want = attention_reference(q, k, v, causal=False)
    got = flash_attention(q, k, v, False, 32, 32, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_reference(causal):
    q, k, v = _qkv(B=1, Tq=32, Tk=32, H=2, D=8)

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=causal) ** 2).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal, 16, 16, True) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ref, g_fl, "qkv"):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=5e-4, atol=5e-4,
            err_msg=f"grad d{name} mismatch",
        )


def test_flash_blocking_degrades_then_rejects():
    # blocks degrade by gcd (48 with a 32 request -> 16-wide tiles) ...
    q, k, v = _qkv(Tq=48, Tk=48)
    got = flash_attention(q, k, v, False, 32, 32, True)
    want = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # ... but truly degenerate lengths (gcd < 8) still raise
    q, k, v = _qkv(Tq=36, Tk=36)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, False, 32, 32, True)


def test_transformer_flash_attention_matches_dense():
    """The flagship transformer with attention='flash' must match the
    dense path in forward loss and gradients (tiny config, interpret).
    T=128 tokens: the r6 default_blocks policy keeps T>=128 on the
    tiled Pallas path (smaller T routes to dense — covered by
    test_transformer_flash_odd_seq_falls_back_to_dense)."""
    from cekirdekler_tpu.models import Transformer, TransformerConfig

    def build(attn):
        cfg = TransformerConfig(
            vocab=64, d_model=32, n_layers=1, n_heads=2, d_ff=64,
            max_seq=128, dtype=jnp.float32, attention=attn,
        )
        return Transformer(cfg)

    tok = jnp.asarray(
        np.random.default_rng(3).integers(0, 64, (2, 129)), jnp.int32
    )
    dense = build("dense")
    params = dense.init(jax.random.PRNGKey(0))
    flash = build("flash")

    def loss(model, p):
        logits = model.apply(p, tok[:, :-1])
        tgt = tok[:, 1:]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, tgt[..., None], axis=-1).mean()

    l_d, g_d = jax.value_and_grad(lambda p: loss(dense, p))(params)
    l_f, g_f = jax.value_and_grad(lambda p: loss(flash, p))(params)
    np.testing.assert_allclose(float(l_f), float(l_d), rtol=1e-5)
    flat_d = jax.tree.leaves(g_d)
    flat_f = jax.tree.leaves(g_f)
    for a, b in zip(flat_d, flat_f):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-3, atol=1e-5)


def test_transformer_flash_non_multiple_seq_len():
    """Sequence lengths that aren't multiples of 128 must work (block is
    chosen to divide T), and a mesh'd model with attention='flash' must
    fall back to a partitionable path instead of crashing."""
    from cekirdekler_tpu.models import Transformer, TransformerConfig

    cfg = TransformerConfig(
        vocab=64, d_model=32, n_layers=1, n_heads=2, d_ff=64,
        max_seq=256, dtype=jnp.float32, attention="flash",
    )
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = jnp.asarray(
        np.random.default_rng(5).integers(0, 64, (1, 200)), jnp.int32
    )
    out = model.apply(params, tok)   # T=200: block gcd(200,128)=8
    assert out.shape == (1, 200, 64)
    assert np.isfinite(np.asarray(out)).all()


def test_transformer_flash_precision_follows_dtype(monkeypatch):
    """bf16 activations must select the r6 "default" (bf16-streamed)
    kernel path; f32 activations keep "highest" (the ~5e-5 dense
    agreement the parity tests pin); attention_precision overrides."""
    import cekirdekler_tpu.ops.flash_attention as fa
    from cekirdekler_tpu.models import Transformer, TransformerConfig

    seen = []
    orig = fa.flash_attention

    def spy(q, k, v, causal=False, block_q=None, block_k=None,
            interpret=None, precision="highest"):
        seen.append(precision)
        return orig(q, k, v, causal, block_q, block_k, interpret, precision)

    monkeypatch.setattr(fa, "flash_attention", spy)
    tok = jnp.asarray(
        np.random.default_rng(4).integers(0, 64, (1, 128)), jnp.int32
    )
    for dtype, override, want in (
        (jnp.bfloat16, None, "default"),
        (jnp.float32, None, "highest"),
        (jnp.float32, "default", "default"),
    ):
        seen.clear()
        cfg = TransformerConfig(
            vocab=64, d_model=32, n_layers=1, n_heads=2, d_ff=64,
            max_seq=128, dtype=dtype, attention="flash",
            attention_precision=override,
        )
        model = Transformer(cfg)
        out = model.apply(model.init(jax.random.PRNGKey(0)), tok)
        assert np.isfinite(np.asarray(out, np.float32)).all()
        assert seen and all(p == want for p in seen), (dtype, override, seen)


def test_auto_block_degenerate_lengths():
    from cekirdekler_tpu.ops.flash_attention import auto_block

    assert auto_block(2048) == 512   # default target: measured sweet spot
    assert auto_block(2048, 128) == 128
    assert auto_block(200) == 8
    assert auto_block(999) is None   # odd: gcd 1 — degenerate
    assert auto_block(17) is None


def test_default_blocks_policy():
    """Default-argument block policy: 512 target by gcd, dense fallback
    (None) whenever only sub-128 (sub-MXU) tiles divide T."""
    from cekirdekler_tpu.ops.flash_attention import default_blocks

    assert default_blocks(4096) == (512, 512)
    assert default_blocks(640) == (128, 128)
    assert default_blocks(2048, 1024) == (512, 512)
    assert default_blocks(96) is None     # 32-wide tiles: dense wins
    assert default_blocks(4104) is None   # 8-wide tiles: dense wins
    assert default_blocks(200) is None


@pytest.mark.parametrize("T", [96, 4104])
def test_flash_default_args_dense_fallback(T):
    """Degrade, don't raise (ADVICE r4 / VERDICT #7): default-argument
    calls at awkward lengths (only sub-128 tiles divide T) fall back to
    dense attention instead of ValueError — and still match the
    reference."""
    q, k, v = _qkv(B=1, Tq=T, Tk=T, H=1, D=8, seed=T)
    got = flash_attention(q, k, v, True)  # default blocks
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_default_args_dense_fallback_differentiable():
    """The dense fallback must be trainable too (plain autodiff)."""
    q, k, v = _qkv(B=1, Tq=96, Tk=96, H=1, D=8, seed=5)

    def loss_fl(q, k, v):
        return (flash_attention(q, k, v, True) ** 2).sum()

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=True) ** 2).sum()

    g = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"fallback grad d{name}")


def test_flash_default_args_tiled_path_640():
    """T=640 under default args stays on the FLASH path (gcd with the
    512 target is 128 — a full MXU tile) and matches the reference."""
    q, k, v = _qkv(B=1, Tq=640, Tk=640, H=1, D=16, seed=6)
    got = flash_attention(q, k, v, True)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("T", [512, 4096])
def test_flash_bf16_default_grad_agreement(T):
    """Regression gate for the r6 bf16 end-to-end default path: grads of
    the bf16-streamed kernels vs the dense f32 reference must stay
    within the documented ~1e-2 flash trade (measured ~3e-3 on this
    configuration)."""
    B, H, D = 1, (2 if T == 512 else 1), 32
    rng = np.random.default_rng(T)
    mk = lambda: jnp.asarray(
        rng.standard_normal((B, T, H, D)).astype(np.float32) * 0.3)
    q, k, v = mk(), mk(), mk()

    def loss_def(q, k, v):
        return flash_attention(q, k, v, True, None, None, None,
                               "default").sum()

    def loss_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    gf = jax.grad(loss_def, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    rel = max(
        float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
        for a, b in zip(gf, gd)
    )
    # 2e-2: measured ~3e-3 here; the documented trade is ~1e-2, the gate
    # leaves rig headroom
    assert rel < 2e-2, f"bf16 default-path grads diverged: rel={rel:.2e}"


def _eqn_out_shapes(closed_jaxpr):
    """All eqn output shapes in a jaxpr, recursing into sub-jaxprs
    (pjit bodies, custom_vjp calls, pallas kernels)."""
    from jax.core import Jaxpr

    shapes = []

    def walk(jx):
        for eqn in jx.eqns:
            for var in eqn.outvars:
                aval = getattr(var, "aval", None)
                if hasattr(aval, "shape"):
                    shapes.append(tuple(aval.shape))
            for p in eqn.params.values():
                for cand in (p if isinstance(p, (list, tuple)) else [p]):
                    if isinstance(cand, Jaxpr):
                        walk(cand)
                    elif isinstance(getattr(cand, "jaxpr", None), Jaxpr):
                        walk(cand.jaxpr)

    walk(closed_jaxpr.jaxpr)
    return shapes


def test_bwd_lse_delta_operands_compact():
    """The r6 bandwidth fix pinned: the fwd residual logsumexp is a
    compact [B*H, T, 1] column, and NO [B*H, T, 128] lane-broadcast
    tile appears anywhere in the backward graph (that layout carried
    128x the needed lse/delta HBM bytes in r5)."""
    from cekirdekler_tpu.ops.flash_attention import _flash_forward

    B, T, H, D = 1, 256, 2, 16
    q, k, v = _qkv(B=B, Tq=T, Tk=T, H=H, D=D, seed=8)
    out, lse, _ = _flash_forward(q, k, v, True, 128, 128, True, "highest",
                                 with_lse=True)
    assert lse.shape == (B * H, T, 1), lse.shape

    def loss(q, k, v):
        return flash_attention(q, k, v, True, 128, 128, True).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    shapes = _eqn_out_shapes(jaxpr)
    assert (B * H, T, 128) not in shapes, (
        "lane-broadcast lse/delta tile reappeared in the backward")
    # positive control: the compact operand layout IS present
    assert (B * H, T, 1) in shapes


def test_transformer_flash_odd_seq_falls_back_to_dense():
    """Odd sequence lengths must not explode the Pallas grid — the flash
    config silently uses the dense path and still matches it."""
    from cekirdekler_tpu.models import Transformer, TransformerConfig

    def build(attn):
        cfg = TransformerConfig(
            vocab=64, d_model=32, n_layers=1, n_heads=2, d_ff=64,
            max_seq=64, dtype=jnp.float32, attention=attn,
        )
        return Transformer(cfg)

    tok = jnp.asarray(
        np.random.default_rng(9).integers(0, 64, (1, 33)), jnp.int32
    )
    dense = build("dense")
    params = dense.init(jax.random.PRNGKey(0))
    out_d = dense.apply(params, tok)
    out_f = build("flash").apply(params, tok)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d), rtol=1e-6)
