"""Flagship transformer tests: forward determinism, loss decreases under
training, sharded multi-device parity with the single-device model."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from cekirdekler_tpu import parallel as par
from cekirdekler_tpu.parallel.mesh import set_mesh

from cekirdekler_tpu.models import Transformer, TransformerConfig


def _cfg(**kw):
    base = dict(
        vocab=64, d_model=32, n_layers=2, n_heads=4, d_ff=64, max_seq=64,
        dtype=jnp.float32,  # f32 on the CPU rig for tight parity checks
    )
    base.update(kw)
    return TransformerConfig(**base)


def _batch(rng, B, T, vocab):
    return {"tokens": jnp.asarray(rng.integers(0, vocab, (B, T + 1)), jnp.int32)}


def test_forward_shapes_and_determinism():
    cfg = _cfg()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 16), jnp.int32)
    a = model.apply(params, toks)
    b = model.apply(params, toks)
    assert a.shape == (2, 16, cfg.vocab)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_training_reduces_loss():
    cfg = _cfg()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = optax.adamw(1e-2)
    opt_state = opt.init(params)
    step = jax.jit(model.make_train_step(opt))
    rng = np.random.default_rng(0)
    batch = _batch(rng, 4, 16, cfg.vocab)  # one fixed batch: loss must drop
    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses


@pytest.mark.parametrize("attention", ["dense", "ring", "ulysses"])
def test_sharded_forward_matches_single_device(attention):
    devs = jax.devices("cpu")[:8]
    mesh = par.make_mesh(devs, dp=2, tp=2, sp=2)
    cfg = _cfg(attention=attention)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)

    want = Transformer(_cfg()).apply(params, toks)  # dense, unsharded

    sharded = model.shard_params(params, mesh)
    toks_s = par.shard_batch(mesh, toks)
    with set_mesh(mesh):
        got = jax.jit(lambda p, t: model.apply(p, t, mesh))(sharded, toks_s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_train_step_sharded_runs_and_matches_loss():
    devs = jax.devices("cpu")[:8]
    mesh = par.make_mesh(devs, dp=2, fsdp=2, tp=2)
    cfg = _cfg()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(2))
    opt = optax.adamw(1e-2)
    rng = np.random.default_rng(2)
    batch = _batch(rng, 4, 16, cfg.vocab)

    # unsharded reference
    step_ref = jax.jit(model.make_train_step(opt))
    p_ref, _, loss_ref = step_ref(params, opt.init(params), batch)

    sharded = model.shard_params(params, mesh)
    batch_s = par.shard_batch(mesh, batch)
    with set_mesh(mesh):
        step = jax.jit(model.make_train_step(opt, mesh))
        p_new, _, loss = step(sharded, opt.init(sharded), batch_s)
    np.testing.assert_allclose(float(loss), float(loss_ref), atol=1e-4)


def test_moe_forward_and_training():
    cfg = _cfg(n_experts=4, moe_every=2)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(4))
    assert "router" in params["blocks"][1] and "router" not in params["blocks"][0]
    opt = optax.adamw(1e-2)
    step = jax.jit(model.make_train_step(opt))
    rng = np.random.default_rng(4)
    batch = _batch(rng, 4, 16, cfg.vocab)
    p, s, l0 = step(params, opt.init(params), batch)
    losses = [float(l0)]
    for _ in range(9):
        p, s, loss = step(p, s, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses


def test_moe_sharded_matches_single_device():
    devs = jax.devices("cpu")[:8]
    mesh = par.make_mesh(devs, dp=2, tp=2, ep=2)
    cfg = _cfg(n_experts=4, moe_every=1)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(5))
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)
    want = model.apply(params, toks)  # unsharded
    sharded = model.shard_params(params, mesh)
    with set_mesh(mesh):
        got = jax.jit(lambda p, t: model.apply(p, t, mesh))(sharded, par.shard_batch(mesh, toks))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_pp_pipelined_matches_sequential():
    devs = jax.devices("cpu")[:8]
    mesh = par.make_mesh(devs, dp=2, pp=2, tp=2)
    cfg = _cfg(pp_stages=2, n_microbatches=2)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(6))  # blocks stacked [L, ...]
    rng = np.random.default_rng(6)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)
    want = model.apply(params, toks)  # mesh=None: sequential over the stack
    sharded = model.shard_params(params, mesh)
    with set_mesh(mesh):
        got = jax.jit(lambda p, t: model.apply(p, t, mesh))(sharded, par.shard_batch(mesh, toks))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_pp_training_reduces_loss():
    devs = jax.devices("cpu")[:4]
    mesh = par.make_mesh(devs, pp=2, tp=2)
    cfg = _cfg(pp_stages=2, n_microbatches=2)
    model = Transformer(cfg)
    params = model.shard_params(model.init(jax.random.PRNGKey(7)), mesh)
    opt = optax.adamw(1e-2)
    rng = np.random.default_rng(7)
    batch = par.shard_batch(mesh, _batch(rng, 4, 16, cfg.vocab))
    with set_mesh(mesh):
        step = jax.jit(model.make_train_step(opt, mesh))
        s = opt.init(params)
        losses = []
        for _ in range(8):
            params, s, loss = step(params, s, batch)
            losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, losses


def test_remat_matches_no_remat():
    cfg = _cfg(remat=True)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(3))
    toks = jnp.zeros((2, 8), jnp.int32)
    got = model.apply(params, toks)
    want = Transformer(_cfg()).apply(params, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_checkpoint_resume_is_deterministic(tmp_path):
    """Checkpoint/resume (SURVEY §5.4 — the subsystem the reference lacks
    entirely): save params+opt_state mid-train, resume in a fresh
    optimizer/step, and the remaining steps must reproduce the original
    run's losses exactly."""
    from cekirdekler_tpu.utils import checkpoint as ckpt

    cfg = _cfg()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(7))
    opt = optax.adamw(1e-2)
    opt_state = opt.init(params)
    step = jax.jit(model.make_train_step(opt))
    rng = np.random.default_rng(7)
    batches = [_batch(rng, 4, 16, cfg.vocab) for _ in range(4)]

    losses = []
    for i, b in enumerate(batches):
        params, opt_state, loss = step(params, opt_state, b)
        losses.append(float(loss))
        if i == 1:
            ckpt.save_pytree(str(tmp_path), 2, {"params": params, "opt": opt_state})

    state = ckpt.load_pytree(
        str(tmp_path), {"params": params, "opt": opt_state}, step=2
    )
    p2, o2 = state["params"], state["opt"]
    resumed = []
    for b in batches[2:]:
        p2, o2, loss = step(p2, o2, b)
        resumed.append(float(loss))
    np.testing.assert_allclose(resumed, losses[2:], rtol=1e-6)


def test_moe_capacity_matches_dense_when_ample():
    """capacity_factor >= E makes dropping impossible: the capacity
    dispatch must reproduce the dense compute-all result exactly, both
    single-device and on the dp x ep mesh."""
    from cekirdekler_tpu.models.moe import moe_ffn, moe_ffn_capacity, moe_ffn_sharded

    rng = np.random.default_rng(7)
    B, T, d, f, E = 2, 16, 32, 64, 4
    x = jnp.asarray(rng.standard_normal((B, T, d)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((d, E)) * 0.1, jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((E, f, d)) * 0.1, jnp.float32)
    want = moe_ffn(x, router, w1, w2)
    got = moe_ffn_capacity(x, router, w1, w2, capacity_factor=float(E))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    mesh = par.make_mesh(jax.devices("cpu")[:4], ep=4)
    got_sh = moe_ffn_sharded(mesh, x, router, w1, w2,
                             capacity_factor=float(E))
    np.testing.assert_allclose(np.asarray(got_sh), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_moe_capacity_drops_overflow_tokens():
    """With capacity 1 token per expert, the FIRST token routed to each
    expert keeps its output and later ones contribute zero."""
    from cekirdekler_tpu.models.moe import moe_ffn_capacity

    rng = np.random.default_rng(8)
    B, T, d, f, E = 1, 8, 16, 32, 2
    x = jnp.asarray(rng.standard_normal((B, T, d)), jnp.float32)
    # zero router: all logits tie, argmax picks expert 0 for every token
    router = jnp.zeros((d, E), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((E, f, d)) * 0.1, jnp.float32)
    # capacity_factor 2/E -> C = ceil(N/E * 2/E)... pick factor so C=1:
    # N=8, E=2 -> C = ceil(4 * cf); cf=0.25 -> C=1
    y = moe_ffn_capacity(x, router, w1, w2, capacity_factor=0.25)
    y = np.asarray(y)
    assert np.abs(y[0, 0]).max() > 0  # first token kept
    assert np.abs(y[0, 1:]).max() == 0  # the rest dropped


def test_moe_capacity_gradients_flow():
    from cekirdekler_tpu.models.moe import moe_ffn_capacity

    rng = np.random.default_rng(9)
    B, T, d, f, E = 2, 8, 16, 32, 4
    x = jnp.asarray(rng.standard_normal((B, T, d)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((d, E)) * 0.1, jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((E, f, d)) * 0.1, jnp.float32)
    g = jax.grad(lambda w1, w2: (
        moe_ffn_capacity(x, router, w1, w2, capacity_factor=2.0) ** 2).sum(),
        argnums=(0, 1))(w1, w2)
    assert all(np.isfinite(np.asarray(a)).all() for a in g)
    assert any(np.abs(np.asarray(a)).max() > 0 for a in g)


def test_moe_capacity_flop_win_on_ep_mesh():
    """The VERDICT r3 #8 criterion: lowered per-step FLOPs of the
    capacity formulation beat dense compute-all at E>=4 on the 8-device
    ep mesh."""
    from cekirdekler_tpu.models.moe import moe_ffn_sharded

    rng = np.random.default_rng(10)
    B, T, d, f, E = 4, 64, 64, 256, 8
    x = jnp.asarray(rng.standard_normal((B, T, d)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((d, E)) * 0.1, jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((E, f, d)) * 0.1, jnp.float32)
    mesh = par.make_mesh(jax.devices("cpu")[:8], ep=8)

    def flops(cf):
        fn = jax.jit(lambda *a: moe_ffn_sharded(mesh, *a, capacity_factor=cf))
        lowered = fn.lower(x, router, w1, w2).compile()
        c = lowered.cost_analysis()
        c = c[0] if isinstance(c, (list, tuple)) else c
        return float(c.get("flops", 0.0))

    dense, cap = flops(0.0), flops(2.0)
    assert dense > 0 and cap > 0
    # dense does T*E_local expert-ffn work per chip; capacity does C*E_local
    # with C = T*cf/E -> expect ~E/cf = 4x fewer total flops (allow slack
    # for routing/scatter overhead)
    assert cap < dense / 2, (dense, cap)


def test_flash_attention_under_batch_sharded_mesh():
    """attention='flash' now runs the Pallas kernels per-shard under a
    dp x fsdp x tp mesh (batch/head sharding never crosses the attention
    reduction); must match the unsharded apply AND train with finite
    grads.  T=128: the smallest length the r6 default_blocks policy
    keeps on the tiled path (sub-128 tiles route to dense)."""
    devs = jax.devices("cpu")[:8]
    mesh = par.make_mesh(devs, dp=2, fsdp=2, tp=2)
    cfg = _cfg(attention="flash", max_seq=128)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(6))
    rng = np.random.default_rng(6)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 128)), jnp.int32)
    want = model.apply(params, toks)  # unsharded (single-chip flash path)
    sharded = model.shard_params(params, mesh)
    with set_mesh(mesh):
        got = jax.jit(lambda p, t: model.apply(p, t, mesh))(
            sharded, par.shard_batch(mesh, toks))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4)
    # one sharded train step: loss finite
    opt = optax.adamw(1e-3)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}
    with set_mesh(mesh):
        step = jax.jit(model.make_train_step(opt, mesh))
        _, _, loss = step(sharded, opt.init(sharded),
                          par.shard_batch(mesh, batch))
    assert np.isfinite(float(loss))


def test_flash_mesh_uneven_heads_falls_back_to_dense():
    """attention='flash' with n_heads not divisible by tp must take the
    GSPMD dense path (which tolerates uneven sharding) instead of a
    shard_map divisibility error.  (An uneven BATCH is rejected upstream
    by shard_batch's explicit sharding — not a flash-path concern.)"""
    devs = jax.devices("cpu")[:4]
    mesh = par.make_mesh(devs, dp=2, tp=2)
    cfg = _cfg(attention="flash", max_seq=64, d_model=48, n_heads=3)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(8))
    rng = np.random.default_rng(8)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 64)), jnp.int32)
    want = Transformer(
        _cfg(max_seq=64, d_model=48, n_heads=3)
    ).apply(params, toks)  # dense, unsharded
    sharded = model.shard_params(params, mesh)
    with set_mesh(mesh):
        got = jax.jit(lambda p, t: model.apply(p, t, mesh))(
            sharded, par.shard_batch(mesh, toks))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4)
