"""Heterogeneous lanes (ISSUE 20): TPU + host-CPU device kinds in ONE
``Cores`` — the prior-seeded split math, the Cores integration (seed,
provenance, warmup rollup), cross-kind compile-cache isolation, the
per-lane-kind attribution rollup, and the hetero_sweep bench section.

The CPU-only container cannot mint real mixed silicon, so the tests
exercise the same seams the sweep does: ``Cores.lane_kinds`` /
``Cores.rate_priors`` are overridable state (the emulation seam — a real
mixed rig fills them from ``jax.Device.device_kind``), and the
compile-cache side is pinned at the key level (``ladder_key`` must
differ in ``device_kind`` alone) plus the live launcher-cache platform
counters."""

import importlib.util
import os
import time

import numpy as np
import pytest

from cekirdekler_tpu import ClArray
from cekirdekler_tpu import hardware as hw
from cekirdekler_tpu.core import NumberCruncher, compilecache
from cekirdekler_tpu.core.balance import equal_split, prior_split
from cekirdekler_tpu.core.compilecache import CACHE, WarmupSpec
from cekirdekler_tpu.hardware import device_rank, platforms, rate_prior
from cekirdekler_tpu.obs.decisions import DECISIONS
from cekirdekler_tpu.trace.attribution import window_report
from cekirdekler_tpu.trace.spans import Span

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

SRC = """
__kernel void inc(__global float* a) {
    int i = get_global_id(0);
    a[i] = a[i] + 1.0f;
}
"""


@pytest.fixture(scope="module")
def devs():
    return platforms().cpus()


def _mark() -> int:
    recs = DECISIONS.snapshot()
    return recs[-1].seq if recs else 0


def _since(mark: int) -> list:
    return [r for r in DECISIONS.snapshot() if r.seq > mark]


# ---------------------------------------------------------------------------
# the rate-prior table (hardware.py)
# ---------------------------------------------------------------------------

def test_rate_prior_table_and_ranking_from_one_table():
    """Satellite 6: device ranking and the balancer prior export from
    ONE table — every accelerator kind beats the host-CPU baseline and
    the rank order is exactly the prior order."""
    assert rate_prior("cpu") == 1.0
    assert rate_prior("host") == 1.0
    assert rate_prior("TPU v5p") > rate_prior("TPU v4") > 1.0
    kinds = ["TPU v5p", "TPU v6e", "TPU v4", "TPU v5e", "cpu"]
    priors = [rate_prior(k) for k in kinds]
    assert priors == sorted(priors, reverse=True)
    ranks = [device_rank(k) for k in kinds]
    assert ranks == sorted(ranks)  # faster kind, better (lower) rank
    # unknown accelerator kinds fall back to the default peak kind's
    # prior, never to the CPU baseline
    assert rate_prior("TPU vNext") > 1.0
    for name in ("rate_prior", "device_rank"):
        assert name in hw.__all__


# ---------------------------------------------------------------------------
# prior_split math + provenance
# ---------------------------------------------------------------------------

def test_prior_split_quantized_within_one_step_of_share():
    for total, step, priors in [
        (1024, 64, [8.0, 1.0]),
        (3072, 128, [55.3, 1.0, 1.0]),
        (8192, 64, [100.0, 1.0]),
        (2048, 256, [2.0, 3.0, 5.0]),
        (640, 64, [1.0, 1.0, 1.0, 1.0, 1.0]),
    ]:
        got = prior_split(total, step, priors)
        assert sum(got) == total
        assert all(r % step == 0 for r in got)
        s = sum(priors)
        for r, p in zip(got, priors):
            assert abs(r - total * p / s) <= step, (got, priors)


def test_prior_split_equal_priors_reproduce_equal_split_bitwise():
    """The homogeneous degenerate case must be BIT-identical to
    equal_split — a same-kind fleet's decision history cannot change
    shape when the prior plumbing is present."""
    for total, step, n in [(1024, 64, 2), (3072, 128, 3), (896, 64, 7)]:
        assert prior_split(total, step, [1.0] * n) == \
            equal_split(total, n, step)
        assert prior_split(total, step, [3.5] * n) == \
            equal_split(total, n, step)


def test_prior_split_records_replayable_decision():
    mark = _mark()
    got = prior_split(1024, 64, [8.0, 1.0], cid=41)
    recs = [r for r in _since(mark) if r.kind == "prior-split"]
    assert len(recs) == 1
    r = recs[0]
    assert r.inputs["priors"] == [8.0, 1.0]
    assert r.inputs["total"] == 1024 and r.inputs["step"] == 64
    assert r.inputs["cid"] == 41
    assert r.outputs["ranges"] == got == [896, 128]


# ---------------------------------------------------------------------------
# Cores integration: seed, provenance, warmup rollup
# ---------------------------------------------------------------------------

def test_cores_prior_seeds_first_split_and_records_provenance(devs):
    """A skewed-prior fleet's FIRST split is the rate-implied one (no
    equal-split warm-up shard), and every load-balance record carries
    the priors so replay/what-if can reconstruct the seeding."""
    n, lr = 4096, 64
    cr = NumberCruncher(devs.subset(2), SRC)
    try:
        # the emulation seam: a real mixed rig gets these from
        # jax.Device.device_kind via hardware.rate_prior
        cr.cores.lane_kinds = ["tpu-emu", "cpu"]
        cr.cores.rate_priors = [8.0, 1.0]
        mark = _mark()
        x = ClArray(np.zeros(n, np.float32), name="hx")
        x.partial_read = True
        for _ in range(3):
            x.compute(cr, 71, "inc", n, lr)
        np.testing.assert_array_equal(np.asarray(x), 3.0)
        expect = prior_split(n, lr, [8.0, 1.0])
        seeds = [r for r in _since(mark) if r.kind == "prior-split"
                 and r.inputs.get("cid") == 71]
        assert len(seeds) == 1
        assert seeds[0].outputs["ranges"] == expect == [3648, 448]
        lbs = [r for r in _since(mark) if r.kind == "load-balance"
               and r.inputs.get("cid") == 71]
        assert lbs, "no load-balance records for the computed cid"
        assert all(r.inputs["rate_prior"] == [8.0, 1.0] for r in lbs)
        # the first balance step starts FROM the seed
        assert lbs[0].inputs["ranges"] == expect
    finally:
        cr.dispose()


def test_cores_homogeneous_fleet_keeps_equal_split_history(devs):
    """Equal priors (the default on a same-kind fleet) must leave the
    decision history EXACTLY as before ISSUE 20: equal first split, no
    prior-split record, rate_prior=None on the balance records."""
    n, lr = 4096, 64
    cr = NumberCruncher(devs.subset(2), SRC)
    try:
        assert cr.cores._skewed_priors() is None
        mark = _mark()
        x = ClArray(np.zeros(n, np.float32), name="hh")
        x.partial_read = True
        for _ in range(3):
            x.compute(cr, 72, "inc", n, lr)
        assert not [r for r in _since(mark) if r.kind == "prior-split"]
        lbs = [r for r in _since(mark) if r.kind == "load-balance"
               and r.inputs.get("cid") == 72]
        assert lbs and all(r.inputs["rate_prior"] is None for r in lbs)
        assert lbs[0].inputs["ranges"] == equal_split(n, 2, lr)
    finally:
        cr.dispose()


def test_cores_lane_kind_state_and_prior_gauges(devs):
    """Every lane gets a kind label and a table-derived prior at
    construction, exported as the ck_lane_rate_prior gauge with the
    ck_lane_kind label (docs/OBSERVABILITY.md)."""
    from cekirdekler_tpu.metrics.registry import REGISTRY

    cr = NumberCruncher(devs.subset(2), SRC)
    try:
        cores = cr.cores
        assert len(cores.lane_kinds) == cores.num_devices
        assert cores.rate_priors == [rate_prior(k)
                                     for k in cores.lane_kinds]
        g = REGISTRY.gauge(
            "ck_lane_rate_prior",
            "table-derived relative-rate prior per lane",
            lane=0, ck_lane_kind=cores.lane_kinds[0])
        assert g.value == cores.rate_priors[0]
    finally:
        cr.dispose()


def test_warmup_rolls_up_ladders_per_device_kind(devs, monkeypatch):
    """Mixed-fleet AOT warmup proof: the warmup report counts ladders
    per DEVICE KIND, so a fleet with a cold kind is visible before
    traffic arrives.  Kind variants are emulated by widening the warm
    target list the same way a real mixed fleet would."""
    n, lr = 1024, 64
    cr = NumberCruncher(devs.subset(2), SRC)
    try:
        cores = cr.cores
        out = cores.warmup(
            [WarmupSpec(kernels=("inc",), params=((n, "float32"),),
                        global_range=n, local_range=lr, values=())])
        assert out["warmed"] == 1 and out["skipped"] == 0
        # homogeneous fleet: one kind, one AOT pass
        assert sum(out["kinds"].values()) == 1
        real = compilecache.warm_targets(cores.workers)
        (platform, donate, kind, device) = real[0]
        monkeypatch.setattr(compilecache, "warm_targets", lambda workers: [
            (platform, donate, kind, device),
            (platform, donate, "tpu-emu", device),
        ])
        out2 = cores.warmup(
            [WarmupSpec(kernels=("inc",), params=((n, "float32"),),
                        global_range=n, local_range=lr, values=())])
        assert out2["kinds"] == {kind: 1, "tpu-emu": 1}
    finally:
        cr.dispose()


# ---------------------------------------------------------------------------
# cross-kind compile-cache isolation
# ---------------------------------------------------------------------------

def test_ladder_key_isolates_device_kinds():
    """The persistent-cache key must differ in device_kind ALONE —
    a CPU lane's ladder can never serve (or evict) a TPU lane's."""
    from cekirdekler_tpu.kernel.registry import KernelProgram

    prog = KernelProgram(SRC)
    spec = WarmupSpec(kernels=("inc",), params=((1024, "float32"),),
                      global_range=1024, local_range=64, values=())
    k_cpu = CACHE.ladder_key(prog, spec, "cpu", False, "cpu")
    k_tpu = CACHE.ladder_key(prog, spec, "cpu", False, "TPU v5p")
    assert k_cpu != k_tpu
    # ... and is stable per kind (the warmup==live pin rides on this)
    assert k_cpu == CACHE.ladder_key(prog, spec, "cpu", False, "cpu")


def test_mixed_fleet_compile_counters_pinned_per_platform(devs):
    """One Cores over emulated cpu+tpu kinds: the launcher cache keys
    by PLATFORM, the mixed fleet's CPU lanes only ever grow the cpu
    counter, nothing is evicted cross-kind, and results stay
    bit-identical with the homogeneous fleet — fused on AND off."""
    n, lr = 4096, 64

    def run(kinds, priors, fused):
        cr = NumberCruncher(devs.subset(2), SRC)
        try:
            if kinds:
                cr.cores.lane_kinds = list(kinds)
                cr.cores.rate_priors = list(priors)
            cr.fused_dispatch = fused
            x = ClArray(np.zeros(n, np.float32), name="mx")
            x.partial_read = True
            cr.enqueue_mode = True
            for _ in range(6):
                x.compute(cr, 73, "inc", n, lr)
            cr.enqueue_mode = False
            counts = cr.cores.program.compiled_counts_by_platform()
            return np.asarray(x).copy(), counts
        finally:
            cr.dispose()

    for fused in (True, False):
        homog, _ = run(None, None, fused)
        mixed, counts = run(["tpu-emu", "cpu"], [8.0, 1.0], fused)
        np.testing.assert_array_equal(mixed, homog)
        np.testing.assert_array_equal(mixed, 6.0)
        # a CPU-platform lane never mints a tpu-platform executable:
        # the only platform key the launcher cache grew is "cpu"
        assert set(counts) == {"cpu"}, counts
        assert counts["cpu"] >= 1


# ---------------------------------------------------------------------------
# per-lane-kind attribution rollup
# ---------------------------------------------------------------------------

def test_window_report_rolls_up_per_lane_kind():
    t0 = 100.0
    spans = [
        Span(kind="kernel", t0=t0 + 0.00, t1=t0 + 0.10, cid=1, lane=0,
             tag=None),
        Span(kind="kernel", t0=t0 + 0.00, t1=t0 + 0.02, cid=1, lane=1,
             tag=None),
        Span(kind="h2d", t0=t0 + 0.10, t1=t0 + 0.15, cid=1, lane=0,
             tag=None),
        # lane-less host span: counted per kind, absent from the rollup
        Span(kind="fence", t0=t0 + 0.15, t1=t0 + 0.20, cid=1, lane=None,
             tag=None),
    ]
    # list form (Cores.lane_kinds by position) and dict form agree
    for lane_kinds in (["TPU v5p", "cpu"], {0: "TPU v5p", 1: "cpu"}):
        rep = window_report(spans, t0, t0 + 0.25, lane_kinds=lane_kinds)
        assert set(rep.per_lane_kind) == {"TPU v5p", "cpu"}
        tpu = rep.per_lane_kind["TPU v5p"]
        assert tpu["count"] == 2 and tpu["lanes"] == {0}
        assert tpu["ms"] == pytest.approx(150.0, abs=1e-6)
        cpu = rep.per_lane_kind["cpu"]
        assert cpu["count"] == 1 and cpu["lanes"] == {1}
        assert cpu["ms"] == pytest.approx(20.0, abs=1e-6)
        d = rep.to_dict()["per_lane_kind"]
        assert d["TPU v5p"]["lanes"] == [0]
        assert "device kind" in rep.table()
    # without the map the rollup stays empty (no lane->kind guess)
    assert window_report(spans, t0, t0 + 0.25).per_lane_kind == {}


# ---------------------------------------------------------------------------
# the hetero_sweep bench section (small-n smoke)
# ---------------------------------------------------------------------------

def test_hetero_sweep_section_smoke(devs):
    spec = importlib.util.spec_from_file_location(
        "ck_hetero_sweep", os.path.join(ROOT, "tools", "hetero_sweep.py"))
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)

    out = sweep.hetero_section(devices=devs, n=16384, local_range=64,
                               iters=3, skew=8.0)
    assert "skipped" not in out, out
    assert out["pinned_model"] is True
    assert out["exact"] is True
    # all four arms computed the same bits — the gate the headline
    # rides on — so the key is minted
    sp = out["hetero_speedup_vs_best_homog"]
    assert sp is not None and sp > 1.0
    # model walls: mixed beats BOTH homogeneous subsets
    assert out["walls"]["mixed"] < out["walls"]["fast_only"]
    assert out["walls"]["mixed"] < out["walls"]["slow_only"]
    # the seed landed within one quantization step of rate-implied
    assert out["prior_split_within_one_step"] is True
    # the traced mixed arm attributed time to BOTH device kinds
    kinds = out["per_lane_kind"]
    assert set(kinds) == {sweep.EMU_FAST_KIND, sweep.EMU_SLOW_KIND}
    assert all(v["count"] > 0 for v in kinds.values())
