"""BlockTuner (core/blocktuner.py): the measured Pallas tile autotuner
that replaced the static ``default_blocks`` heuristic as the flash
default-argument block chooser (ISSUE 16).

Lifecycle coverage mirrors tests/test_stream.py's TransferTuner suite:
determinism, wall monotonicity, hysteresis no-flap, measuring-run ->
engage -> retune, ProfileStore-seeded warm start, executable-geometry
stability across a hysteresis hold — plus the flash integration pins
(explicit blocks bypass the tuner bit-identically, cold default-arg
equals the static pair bit-identically), the fused-QKV / one-shot
kernel variants, the hardware.py roofline-peak table (ISSUE 16
satellite), and the replayable ``block-retune`` decision provenance
(golden fixture green, tampered fixture names the first divergent
seq)."""

import importlib.util
import json
import os

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402

from cekirdekler_tpu.core import blocktuner as bt  # noqa: E402
from cekirdekler_tpu.core.blocktuner import (  # noqa: E402
    BLOCK_CANDIDATES,
    HYSTERESIS_FRAC,
    BlockTuner,
    block_transition,
    clamp_blocks,
    legal_block_grid,
    orient_block_grid,
)
from cekirdekler_tpu.obs import replay as replay_mod  # noqa: E402
from cekirdekler_tpu.obs.decisions import (  # noqa: E402
    DECISIONS,
    load_decision_log,
)
from cekirdekler_tpu.ops.flash_attention import (  # noqa: E402
    default_blocks,
    flash_attention,
    fused_qkv,
    fused_qkv_attention,
)
from cekirdekler_tpu.parallel.attention import attention_reference  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
GOLDEN = os.path.join(HERE, "fixtures_decisions",
                      "golden_block_retune.jsonl")
SIG = "flash_attention.bf16_default"
#: the key a default-precision ("highest") flash call asks the tuner for
HSIG = "flash_attention.highest"


def _load_tool(name, relpath):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _qkv(B=1, T=256, H=1, D=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    return mk(), mk(), mk()


def _tuner(**kw):
    kw.setdefault("device_kind", "test-rig")
    return BlockTuner(**kw)


# ---------------------------------------------------------------------------
# the pure surface: grid legality, orientation, clamping, transition
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T", [96, 128, 200, 256, 640, 999, 1024, 4096, 4104])
def test_legal_grid_empty_iff_static_policy_falls_dense(T):
    """The equivalence the default path is built on: the tuner's legal
    grid is empty exactly when ``default_blocks`` returns None — the
    two policies agree on WHEN tiling is legal and only ever disagree
    on WHICH legal tile to run."""
    assert (not legal_block_grid(T, T)) == (default_blocks(T, T) is None)


def test_legal_grid_contents():
    assert legal_block_grid(256, 256) == (
        (128, 128), (128, 256), (256, 128), (256, 256))
    # per-axis legality: Tq and Tk divide independently
    assert legal_block_grid(128, 256) == ((128, 128), (128, 256))
    assert legal_block_grid(640, 640) == ((128, 128),)  # only 128 | 640
    assert legal_block_grid(96, 96) == ()               # sub-floor only


def test_orient_block_grid():
    grid = legal_block_grid(512, 512)
    comp = orient_block_grid(grid, "compute")
    mem = orient_block_grid(grid, "memory")
    assert set(comp) == set(mem) == set(grid)  # reorders, never drops
    assert comp[0] == (512, 512) and mem[0] == (128, 128)
    areas = [p[0] * p[1] for p in comp]
    assert areas == sorted(areas, reverse=True)
    assert orient_block_grid(grid, None) == tuple(grid)


def test_clamp_blocks():
    grid = legal_block_grid(512, 512)
    assert clamp_blocks((256, 256), grid) == (256, 256)  # member
    assert clamp_blocks((1024, 256), grid) == (512, 256)  # nearest
    assert clamp_blocks((2048, 2048), grid) == (512, 512)
    assert clamp_blocks(None, grid) is None
    assert clamp_blocks((256, 256), ()) is None


def test_transition_deterministic_and_order_free():
    grid = legal_block_grid(512, 512)
    walls = [((256, 256), 1.0), ((128, 128), 2.0), ((512, 512), 1.5)]
    got = block_transition((128, 128), walls, grid)
    for _ in range(3):
        assert block_transition((128, 128), walls, grid) == got
    assert block_transition((128, 128), list(reversed(walls)), grid) == got


def test_transition_cold_vocabulary():
    grid = legal_block_grid(512, 512)
    assert block_transition(None, [], ()) == (None, "no-legal-grid")
    assert block_transition(None, [], grid) == (None, "cold")
    assert block_transition(None, [], grid, fallback=(256, 256)) == \
        ((256, 256), "cold-fallback")
    # the seed outranks the fallback, and clamps onto the grid
    assert block_transition(None, [], grid, seed=(2048, 256),
                            fallback=(256, 256)) == ((512, 256), "store-seed")
    # a wall for a pair OUTSIDE the grid is ignored (stale geometry)
    assert block_transition(None, [((64, 64), 0.1)], grid,
                            fallback=(256, 256)) == \
        ((256, 256), "cold-fallback")


def test_transition_wall_monotonicity():
    """Raising a loser's wall never flips the choice toward it;
    lowering the winner's wall never unseats it."""
    grid = legal_block_grid(512, 512)
    cur = (256, 256)
    walls = {(256, 256): 1.0, (128, 128): 2.0, (512, 512): 1.5}
    assert block_transition(cur, walls.items(), grid)[0] == cur
    for worse in (2.5, 5.0, 50.0):
        w = dict(walls)
        w[(128, 128)] = worse
        assert block_transition(cur, w.items(), grid)[0] == cur
    for better in (0.9, 0.5, 0.01):
        w = dict(walls)
        w[(256, 256)] = better
        assert block_transition(cur, w.items(), grid) == (cur, "steady")


def test_transition_hysteresis_no_flap():
    """±noise inside the hysteresis band can NEVER flap an engaged,
    measured choice; a real cliff still switches it."""
    grid = legal_block_grid(512, 512)
    cur = (256, 256)
    band = 1.0 - HYSTERESIS_FRAC
    for frac in (1.0, 0.99, band + 1e-9):
        walls = [((256, 256), 1.0), ((512, 512), frac)]
        choice, why = block_transition(cur, walls, grid)
        assert (choice, why) == (cur, "hysteresis-hold" if frac < 1.0
                                 else "steady"), frac
    choice, why = block_transition(
        cur, [((256, 256), 1.0), ((512, 512), band - 0.01)], grid)
    assert (choice, why) == ((512, 512), "model")


def test_transition_unmeasured_incumbent_yields_to_first_measurement():
    """A store-seeded or fallback-engaged incumbent has no wall of its
    own: the first measurement set takes over without hysteresis (there
    is no incumbent wall to defend)."""
    grid = legal_block_grid(512, 512)
    choice, why = block_transition(
        (512, 512), [((256, 256), 1.0)], grid)
    assert (choice, why) == ((256, 256), "measuring")
    choice, why = block_transition(
        (512, 512), [((512, 512), 1.0)], grid)
    assert (choice, why) == ((512, 512), "steady")


# ---------------------------------------------------------------------------
# the stateful wrapper: lifecycle, measuring run, store seam, metrics
# ---------------------------------------------------------------------------

def test_tuner_cold_fallback_then_measured_takeover():
    t = _tuner()
    assert t.choose(SIG, 512, 512, fallback=(512, 512)) == (512, 512)
    assert t.retunes == 1  # first engagement counts
    t.observe(SIG, 512, 512, (256, 256), 1.0)
    assert t.choose(SIG, 512, 512) == (256, 256)
    assert t.retunes == 2
    # steady re-asks don't retune
    assert t.choose(SIG, 512, 512) == (256, 256)
    assert t.retunes == 2


def test_tuner_hysteresis_hold_keeps_retunes_flat():
    t = _tuner()
    t.observe(SIG, 512, 512, (256, 256), 1.0)
    t.choose(SIG, 512, 512, fallback=(512, 512))
    before = t.retunes
    for noise in (0.97, 1.02, 0.95, 1.04):
        t.observe(SIG, 512, 512, (512, 512), noise)
        assert t.choose(SIG, 512, 512) == (256, 256)
    assert t.retunes == before


def test_tuner_ema_tracks_weather():
    t = _tuner(ema=0.5)
    t.observe(SIG, 512, 512, (256, 256), 2.0)
    t.observe(SIG, 512, 512, (256, 256), 1.0)
    snap = t.snapshot()
    (key,) = snap
    assert snap[key]["walls"][(256, 256)] == pytest.approx(1.5)


def test_measuring_run_engages_then_cliff_retunes():
    walls = {(128, 128): 2.0, (128, 256): 1.8, (128, 512): 1.6,
             (256, 128): 1.7, (256, 256): 0.9, (256, 512): 1.1}
    t = _tuner()
    out = t.measuring_run(SIG, 512, 512,
                          lambda bq, bk: walls[(bq, bk)])
    assert out["skipped"] is None
    assert [m["block_q"] for m in out["measured"]] == \
        [p[0] for p in list(legal_block_grid(512, 512))[:6]]
    assert out["chosen"] == (256, 256)
    # a later cliff on another candidate retunes past hysteresis
    t.observe(SIG, 512, 512, (512, 512), 0.5)
    assert t.choose(SIG, 512, 512) == (512, 512)


def test_measuring_run_orients_by_bound_under_cap():
    seen = []

    def runner(bq, bk):
        seen.append((bq, bk))
        return 1.0

    t = _tuner()
    t.measuring_run(SIG, 2048, 2048, runner, bound="compute", limit=3)
    assert len(seen) == 3
    areas = [p[0] * p[1] for p in seen]
    assert areas == sorted(areas, reverse=True)  # big tiles first


def test_store_seeded_warm_start_skips_measuring_run(tmp_path):
    """The whole point of persisting profiles: a key with store rows
    engages the stored best WITHOUT paying the measuring walk."""
    from cekirdekler_tpu.trace.device import ProfileStore

    store = ProfileStore(str(tmp_path))
    shape = (2, 4096, 8, 64)
    store.put(SIG, shape, (512, 512), {"device_ms": 1.4})
    store.put(SIG, shape, (1024, 512), {"device_ms": 0.9})
    store.put(SIG, shape, (256, 256), {"device_ms": 2.2})
    assert store.best_blocks(SIG, shape) == (1024, 512)

    t = _tuner(store=store)

    def must_not_run(bq, bk):  # pragma: no cover - the assertion
        raise AssertionError("store-seeded key paid a measuring walk")

    out = t.measuring_run(SIG, 4096, 4096, must_not_run, shape=shape)
    assert out["skipped"] == "store-seed"
    assert out["chosen"] == (1024, 512)
    assert out["measured"] == []


def test_store_seed_clamps_foreign_geometry(tmp_path):
    """Rows inherited from a rig whose best pair is illegal HERE snap
    onto the legal grid instead of being trusted verbatim."""
    from cekirdekler_tpu.trace.device import ProfileStore

    store = ProfileStore(str(tmp_path))
    shape = (1, 640, 8, 64)
    store.put(SIG, shape, (512, 512), {"device_ms": 1.0})
    t = _tuner(store=store)
    # only (128, 128) is legal at T=640
    assert t.choose(SIG, 640, 640, shape=shape) == (128, 128)


def test_invalidate_drops_state_and_reengages():
    t = _tuner()
    t.observe(SIG, 512, 512, (256, 256), 1.0)
    t.choose(SIG, 512, 512)
    t.observe("other.sig", 512, 512, (128, 128), 1.0)
    t.choose("other.sig", 512, 512)
    t.on_invalidate(SIG)
    snap = t.snapshot()
    assert all(k[0] == "other.sig" for k in snap)
    # the dropped key re-engages from scratch
    assert t.choose(SIG, 512, 512, fallback=(512, 512)) == (512, 512)


def test_tuner_metrics_move():
    from cekirdekler_tpu.metrics.registry import REGISTRY

    c_choose = REGISTRY.counter("ck_block_choose_total")
    c_ret = REGISTRY.counter("ck_block_retunes_total")
    c_meas = REGISTRY.counter("ck_block_measure_runs_total")
    v0, r0, m0 = c_choose.value, c_ret.value, c_meas.value
    t = _tuner()
    t.measuring_run(SIG, 512, 512, lambda bq, bk: 1.0, limit=2)
    assert c_choose.value > v0
    assert c_ret.value > r0
    assert c_meas.value == m0 + 1


def test_concurrent_choose_observe_consistent():
    """The TransferTuner lock discipline: concurrent observers and
    choosers never tear state, and the final choice is the measured
    best."""
    import threading

    t = _tuner()
    errs = []

    def obs():
        try:
            for i in range(200):
                t.observe(SIG, 512, 512, (256, 256), 1.0 + (i % 3) * 0.01)
                t.observe(SIG, 512, 512, (512, 512), 3.0)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    def cho():
        try:
            for _ in range(200):
                c = t.choose(SIG, 512, 512, fallback=(512, 512))
                assert c in ((512, 512), (256, 256))
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=f) for f in (obs, obs, cho, cho)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs
    assert t.choose(SIG, 512, 512) == (256, 256)


# ---------------------------------------------------------------------------
# flash integration: default-arg engages the tuner, explicit bypasses
# ---------------------------------------------------------------------------

def test_flash_explicit_blocks_bypass_tuner(monkeypatch):
    calls = []
    t = _tuner()
    orig = t.choose

    def spy(*a, **kw):
        calls.append(a)
        return orig(*a, **kw)

    monkeypatch.setattr(t, "choose", spy)
    monkeypatch.setattr(bt, "TUNER", t)
    q, k, v = _qkv(T=256)
    flash_attention(q, k, v, False, 128, 128, True)
    assert calls == []  # explicit blocks never consult the tuner
    flash_attention(q, k, v, False, None, None, True)
    assert len(calls) == 1  # the default-arg path does


def test_flash_cold_default_arg_bit_identical_to_static(monkeypatch):
    """Acceptance pin: with no measurements and no store rows, the
    default-argument call runs EXACTLY the static ``default_blocks``
    geometry — bit-identical output, not merely close."""
    monkeypatch.setattr(bt, "TUNER", _tuner())
    q, k, v = _qkv(T=256, D=16, seed=3)
    fb = default_blocks(256, 256)
    got = flash_attention(q, k, v, True, None, None, True)
    want = flash_attention(q, k, v, True, fb[0], fb[1], True)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_flash_default_arg_follows_engaged_choice(monkeypatch):
    """A tuned choice changes what the default path runs: bit-identical
    to the SAME geometry called explicitly."""
    t = _tuner()
    monkeypatch.setattr(bt, "TUNER", t)
    t.observe(HSIG, 256, 256, (128, 256), 0.5)
    t.observe(HSIG, 256, 256, (256, 256), 2.0)
    q, k, v = _qkv(T=256, D=16, seed=4)
    got = flash_attention(q, k, v, False, None, None, True)
    want = flash_attention(q, k, v, False, 128, 256, True)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_hold_keeps_lowered_geometry_retune_changes_it(monkeypatch):
    """Executable-cache accounting across the tuner lifecycle: a
    hysteresis hold keeps the traced block geometry (same lowering →
    the jit cache stays warm), a past-band retune changes it (ONE new
    executable, bought by a real cliff, not noise)."""
    import re

    t = _tuner()
    monkeypatch.setattr(bt, "TUNER", t)
    q, k, v = _qkv(T=256, D=8)

    def jaxpr():
        s = str(jax.make_jaxpr(lambda q, k, v: flash_attention(
            q, k, v, False, None, None, True))(q, k, v))
        return re.sub(r"0x[0-9a-f]+", "0x", s)  # drop object addresses

    j0 = jaxpr()  # cold: engages default_blocks (256, 256)
    r0 = t.retunes
    t.observe(HSIG, 256, 256, (256, 256), 1.0)
    t.observe(HSIG, 256, 256, (128, 128), 0.95)  # 5% < the 8% band
    assert jaxpr() == j0  # hold → identical lowering
    assert t.retunes == r0
    t.observe(HSIG, 256, 256, (128, 128), 0.5)
    t.observe(HSIG, 256, 256, (128, 128), 0.5)
    j1 = jaxpr()
    assert t.retunes == r0 + 1
    assert j1 != j0  # the retune IS a new geometry


def test_flash_tuner_failure_degrades_to_static(monkeypatch):
    """Telemetry plumbing must never sink the math: a tuner that raises
    leaves the default path on the static pair."""
    t = _tuner()

    def boom(*a, **kw):
        raise RuntimeError("tuner plumbing failure")

    monkeypatch.setattr(t, "choose", boom)
    monkeypatch.setattr(bt, "TUNER", t)
    q, k, v = _qkv(T=256, D=16, seed=5)
    got = flash_attention(q, k, v, True, None, None, True)
    fb = default_blocks(256, 256)
    want = flash_attention(q, k, v, True, fb[0], fb[1], True)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# kernel-level MFU variants: fused QKV, one-shot softmax
# ---------------------------------------------------------------------------

def test_fused_qkv_bit_identical_to_separate_projections():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, 64, 32)), jnp.float32)
    wq = jnp.asarray(rng.standard_normal((32, 48)), jnp.float32)
    wk = jnp.asarray(rng.standard_normal((32, 48)), jnp.float32)
    wv = jnp.asarray(rng.standard_normal((32, 48)), jnp.float32)
    q, k, v = fused_qkv(x, wq, wk, wv)
    for got, w, name in ((q, wq, "q"), (k, wk, "k"), (v, wv, "v")):
        assert np.array_equal(np.asarray(got), np.asarray(x @ w)), name


def test_fused_qkv_attention_matches_reference():
    rng = np.random.default_rng(8)
    B, T, E, H, D = 1, 256, 32, 2, 16
    x = jnp.asarray(rng.standard_normal((B, T, E)) * 0.3, jnp.float32)
    mk = lambda: jnp.asarray(rng.standard_normal((E, H * D)) * 0.3,
                             jnp.float32)
    wq, wk, wv = mk(), mk(), mk()
    got = fused_qkv_attention(x, wq, wk, wv, H, causal=True,
                              interpret=True)
    q = (x @ wq).reshape(B, T, H, D)
    k = (x @ wk).reshape(B, T, H, D)
    v = (x @ wv).reshape(B, T, H, D)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_one_shot_softmax_single_kblock_matches_reference(causal):
    """block_k == Tk runs the one-shot softmax re-materialization (no
    running-max rescale) — values and grads must match the dense
    reference like any other geometry."""
    q, k, v = _qkv(T=128, D=8, seed=11)
    got = flash_attention(q, k, v, causal, 128, 128, True)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    def loss_fl(q, k, v):
        return (flash_attention(q, k, v, causal, 128, 128, True) ** 2).sum()

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=causal) ** 2).sum()

    g = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"one-shot grad d{name}")


def test_one_shot_agrees_with_two_step_geometry():
    q, k, v = _qkv(T=128, D=8, seed=12)
    one = flash_attention(q, k, v, False, 128, 128, True)
    two = flash_attention(q, k, v, False, 128, 64, True)
    np.testing.assert_allclose(np.asarray(one), np.asarray(two),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# roofline peaks from the hardware table (ISSUE 16 satellite)
# ---------------------------------------------------------------------------

def test_device_peak_table_pins_v5e_numbers():
    from cekirdekler_tpu.errors import DeviceSelectionError
    from cekirdekler_tpu.hardware import DEVICE_PEAKS, device_peaks

    assert DEVICE_PEAKS["TPU v5e"] == (197.0, 819.0)
    assert DEVICE_PEAKS["TPU v5 lite"] == (197.0, 819.0)
    tf, gb, kind = device_peaks("TPU v4")
    assert (tf, gb, kind) == (275.0, 1228.0, "TPU v4")
    # a kind the table does not list (the CPU rig, a typo) is an error,
    # never an assumed v5e roof
    for unknown in ("cpu", "nonsense"):
        with pytest.raises(DeviceSelectionError, match=unknown):
            device_peaks(unknown)
    with pytest.raises(DeviceSelectionError, match="cpu"):
        device_peaks()  # this rig's first device is a CPU


def test_roofline_row_peaks_come_from_the_named_kind():
    """Peaks resolve by device kind: the v5e row equals the explicit
    public-spec numbers, another kind is judged against its own roof,
    and the CPU rig (no table entry) gets no row at all."""
    from cekirdekler_tpu.errors import DeviceSelectionError
    from cekirdekler_tpu.trace.device import roofline_row

    auto = roofline_row(1e12, 1e9, 5.0, device_kind="TPU v5 lite")
    pinned = roofline_row(1e12, 1e9, 5.0, peak_tflops=197.0,
                          peak_gbps=819.0)
    assert pinned["peak_kind"] == "override"
    assert auto["peak_kind"] == "TPU v5 lite"
    for key in ("attained_tflops", "mfu", "bound", "frac_of_roof",
                "intensity_flop_per_byte"):
        assert auto[key] == pinned[key], key
    v4 = roofline_row(1e12, 1e9, 5.0, device_kind="TPU v4")
    assert v4["peak_kind"] == "TPU v4"
    assert v4["mfu"] < auto["mfu"]  # judged against a taller roof
    with pytest.raises(DeviceSelectionError):
        roofline_row(1e12, 1e9, 5.0)


# ---------------------------------------------------------------------------
# decision provenance: live records replay, golden fixture, tamper
# ---------------------------------------------------------------------------

def _mark() -> int:
    recs = DECISIONS.snapshot()
    return recs[-1].seq if recs else 0


def _since(mark: int):
    return [r for r in DECISIONS.snapshot() if r.seq > mark]


def test_live_retunes_replay_bit_identically():
    mark = _mark()
    t = _tuner()
    t.choose(SIG, 512, 512, fallback=(512, 512))     # cold-fallback
    t.observe(SIG, 512, 512, (256, 256), 1.0)
    t.choose(SIG, 512, 512)                          # measuring takeover
    t.observe(SIG, 512, 512, (512, 512), 0.5)
    t.observe(SIG, 512, 512, (512, 512), 0.5)
    t.choose(SIG, 512, 512)                          # model retune
    rows = [r for r in _since(mark) if r.kind == "block-retune"]
    assert [r.outputs["why"] for r in rows] == \
        ["cold-fallback", "measuring", "model"]
    verdict = replay_mod.verify_records(rows)
    assert verdict["ok"], verdict["first_divergence"]
    assert verdict["replayed"] == 3


def test_hold_records_nothing():
    mark = _mark()
    t = _tuner()
    t.observe(SIG, 512, 512, (256, 256), 1.0)
    t.choose(SIG, 512, 512)
    after_engage = len([r for r in _since(mark)
                        if r.kind == "block-retune"])
    t.observe(SIG, 512, 512, (512, 512), 0.95)
    t.choose(SIG, 512, 512)  # hysteresis-hold
    t.choose(SIG, 512, 512)  # steady
    held = [r for r in _since(mark) if r.kind == "block-retune"]
    assert len(held) == after_engage  # no choice change -> no record


def test_golden_block_fixture_replays_bit_identically():
    rows = load_decision_log(GOLDEN)
    assert len(rows) == 6
    whys = [r.outputs["why"] for r in rows]
    assert "store-seed" in whys and "measuring" in whys \
        and "model" in whys and "cold-fallback" in whys
    verdict = replay_mod.verify_records(rows)
    assert verdict["ok"], verdict["first_divergence"]
    assert verdict["replayed"] == len(rows)


def test_tampered_block_fixture_names_first_divergent_seq():
    rows = [r.to_row() for r in load_decision_log(GOLDEN)]
    tampered = json.loads(json.dumps(rows))
    victim = next(r for r in tampered
                  if r["outputs"]["why"] == "model")
    victim["outputs"]["block_q"] = 128  # the transition chose 512
    verdict = replay_mod.verify_records(tampered)
    assert not verdict["ok"]
    assert verdict["first_divergence"]["seq"] == victim["seq"]
    assert verdict["first_divergence"]["kind"] == "block-retune"


def test_perturbed_hysteresis_knob_is_divergence(monkeypatch):
    """The recorded hysteresis travels IN the record, so replay is
    knob-proof there — but a grid-arithmetic change (the candidate
    table) must fail replay and name the seq."""
    rows = load_decision_log(GOLDEN)
    assert replay_mod.verify_records(rows)["ok"]
    monkeypatch.setattr(bt, "BLOCK_CANDIDATES", (128,))
    # the recorded grid also travels in the record: replay rebuilds the
    # transition from recorded inputs, so even this stays green — the
    # record is self-contained by design
    assert replay_mod.verify_records(rows)["ok"]


def test_ckreplay_cli_verify_and_whatif_block_grid(capsys):
    ckreplay = _load_tool("ck_replay_tool_bt", "tools/ckreplay.py")
    assert ckreplay.main(["verify", GOLDEN]) == 0
    out = capsys.readouterr().out
    assert "block-retune=6" in out
    assert ckreplay.main(
        ["whatif", GOLDEN, "--set", "block_grid=128x256"]) == 0
    out = capsys.readouterr().out
    assert "block choices:" in out
    with pytest.raises(SystemExit):
        ckreplay.parse_overrides("block_grid=bogus")


def test_whatif_block_grid_counterfactual():
    rows = load_decision_log(GOLDEN)
    rep = replay_mod.whatif(rows, {"block_grid": (128, 256)})
    assert len(rep["block_choices"]) == 6
    assert rep["block_choices_changed"] >= 1
    for ch in rep["block_choices"]:
        assert set(ch) >= {"seq", "kernel_sig", "factual",
                           "counterfactual", "why"}
    # restricting the grid to the factual candidates changes nothing
    same = replay_mod.whatif(rows, {"block_grid": BLOCK_CANDIDATES})
    assert same["block_choices_changed"] == 0
