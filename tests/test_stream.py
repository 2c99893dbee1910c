"""Streamed partition transfers (ISSUE 5): ladder-aligned chunk
planning, the online transfer autotuner's contract (deterministic,
monotone in link latency, re-tunes on re-partition), and the acceptance
pin that the chunked double-buffered path is BIT-identical to the
monolithic path on mandelbrot and accumulating n-body, fused dispatch on
AND off.  Tuner tests are pure host logic — timings are synthetic inputs
(`observe`), never clocks — so they are exact on any rig."""

import numpy as np
import pytest

from cekirdekler_tpu import ClArray
from cekirdekler_tpu.core import NumberCruncher
from cekirdekler_tpu.core.phase import classify, tuner_kernel_key
from cekirdekler_tpu.core.stream import (
    BOOTSTRAP_BYTES,
    BOOTSTRAP_CHUNKS,
    CHUNK_CANDIDATES,
    TransferTuner,
    chunk_plan,
)
from cekirdekler_tpu.hardware import platforms


@pytest.fixture(scope="module")
def devs():
    return platforms().cpus()


# ---------------------------------------------------------------------------
# chunk planning: step·2^k geometry (every chunk a ladder cache hit)
# ---------------------------------------------------------------------------

def test_chunk_plan_sizes_are_ladder_aligned():
    for size, step, target in ((4096, 64, 8), (4096, 64, 5), (832, 64, 4),
                               (256, 256, 4), (7 * 64, 64, 16)):
        plan = chunk_plan(size, step, target)
        off = 0
        for coff, csz in plan:
            assert coff == off  # ascending, gap-free
            units = csz // step
            assert csz % step == 0
            assert units & (units - 1) == 0, (csz, step)  # step·2^k
            off += csz
        assert off == size  # exact cover

def test_chunk_plan_reaches_target_when_splittable():
    plan = chunk_plan(4096, 64, 8)
    assert len(plan) == 8
    # unsplittable floor: every chunk already one step
    assert len(chunk_plan(256, 256, 4)) == 1
    assert len(chunk_plan(4 * 64, 64, 99)) == 4


def test_chunk_plan_rejects_non_multiple():
    with pytest.raises(ValueError):
        chunk_plan(100, 64, 4)
    with pytest.raises(ValueError):
        chunk_plan(128, 0, 2)


# ---------------------------------------------------------------------------
# the autotuner's contract
# ---------------------------------------------------------------------------

MIB = float(1 << 20)


def _teach(t: TransferTuner, lane=0, key=("k",), nbytes=1 << 22,
           u=10.0, c=10.0, d=10.0):
    """One monolithic measuring run's observation."""
    t.observe(lane, key, nbytes, u, c, d, chunks=1)


def test_tuner_first_contact_is_the_measuring_run():
    t = TransferTuner()
    assert t.choose(0, ("k",), 1 << 22, max_chunks=64) == 1


def test_tuner_deterministic_under_fixed_timings():
    def build():
        t = TransferTuner()
        t.seed_link(0, 2.0, 2.0)
        _teach(t, u=12.0, c=9.0, d=11.0)
        t.observe(0, ("k",), 1 << 22, 11.0, 0.0, 10.0, chunks=4,
                  wall_ms=20.0)
        return t

    a, b = build(), build()
    for _ in range(3):  # choose() has no internal state advance
        ca = a.choose(0, ("k",), 1 << 22, max_chunks=64)
        cb = b.choose(0, ("k",), 1 << 22, max_chunks=64)
        assert ca == cb
        assert ca == a.choose(0, ("k",), 1 << 22, max_chunks=64)
    assert a.lane_overhead_ms(0) == b.lane_overhead_ms(0)


def test_tuner_chunk_count_monotone_in_link_latency():
    """Scaling synthetic link latency up (U, D grow, compute fixed)
    never DECREASES the chosen chunk count — more transfer to hide
    justifies more (or equal) pipeline granularity, never less."""
    chosen = []
    for scale in (0.25, 0.5, 1.0, 2.0, 4.0, 8.0, 16.0):
        t = TransferTuner()
        _teach(t, u=4.0 * scale, c=6.0, d=4.0 * scale)
        chosen.append(t.choose(0, ("k",), 1 << 22, max_chunks=1024))
    assert chosen == sorted(chosen), chosen
    assert chosen[-1] > chosen[0]  # the sweep actually moves the choice


def test_tuner_more_overhead_never_more_chunks():
    """The dual monotonicity: a lane whose learned per-chunk cost grows
    never gets MORE chunks out of the model."""
    chosen = []
    for ov in (0.01, 0.1, 1.0, 5.0, 50.0):
        t = TransferTuner(overhead_ms=ov)
        _teach(t, u=10.0, c=10.0, d=10.0)
        chosen.append(t.choose(0, ("k",), 1 << 22, max_chunks=1024))
    assert chosen == sorted(chosen, reverse=True), chosen
    assert chosen[0] > 1 and chosen[-1] == 1


def test_tuner_retunes_on_repartition():
    t = TransferTuner()
    t.seed_link(0, 3.0, 3.0)
    _teach(t, u=20.0, c=5.0, d=20.0)
    assert t.choose(0, ("k",), 1 << 22, max_chunks=1024) > 1
    t.on_repartition()
    assert t.retunes == 1
    # observations dropped: the compute key is back to first contact
    # (the monolithic measuring run) ...
    assert t.choose(0, ("k",), 1 << 22, max_chunks=1024) == 1
    # ... but the duplex-probe link seed SURVIVES: no-compute keys keep
    # modeling from link physics (3 ms/MiB each way on 4 MiB >> any
    # per-chunk overhead, so the model still wants chunks)
    assert t.choose(0, "flush-d2h", 1 << 22, 1024, has_compute=False) > 1


def test_tuner_flip_back_to_one_chunk_remeasures():
    """The module docstring's freshness promise: when the model flips a
    key from chunked back to 1 chunk, the observation is dropped so the
    flip's run is a fresh fenced measuring run.  Without it the 1-chunk
    regime is clamp-only (estimates can only FALL) and a link that
    later slows could never re-engage streaming."""
    t = TransferTuner(overhead_ms=2.0)
    _teach(t, u=10.0, c=10.0, d=10.0)
    assert t.choose(0, ("k",), 1 << 22, max_chunks=1024) > 1
    # transfers shrink until hideable rest < per-chunk overhead: the
    # model now prefers monolithic (fenced EMA pulls U/D down)
    for _ in range(4):
        t.observe(0, ("k",), 1 << 22, 0.0, 10.0, 0.0, chunks=1,
                  fenced=True)
    assert t.choose(0, ("k",), 1 << 22, max_chunks=1024) == 1
    # the flip dropped the obs — next contact is a measuring run again
    assert not t.has_obs(0, ("k",), 1 << 22)
    # and re-teaching transfer-dominant numbers re-engages streaming
    _teach(t, u=50.0, c=5.0, d=50.0)
    assert t.choose(0, ("k",), 1 << 22, max_chunks=1024) > 1


def test_tuner_clamp_only_streak_remeasures():
    """A key parked at 1 chunk sees only unfenced clamp-only walls —
    blind to a link that got SLOWER.  REMEASURE_AFTER consecutive
    clamp-only observations drop the key for a fresh measuring run."""
    from cekirdekler_tpu.core.stream import REMEASURE_AFTER

    t = TransferTuner(overhead_ms=5.0)
    # compute-dominant from the start: choice is 1, no flip ever fires
    _teach(t, u=1.0, c=100.0, d=1.0)
    assert t.choose(0, ("k",), 1 << 22, max_chunks=1024) == 1
    for _ in range(REMEASURE_AFTER - 1):
        t.observe(0, ("k",), 1 << 22, 0.0, 0.0, 0.0, chunks=1,
                  wall_ms=102.0)
        assert t.has_obs(0, ("k",), 1 << 22)
    t.observe(0, ("k",), 1 << 22, 0.0, 0.0, 0.0, chunks=1, wall_ms=102.0)
    assert not t.has_obs(0, ("k",), 1 << 22)


def test_tuner_no_compute_bootstrap_without_seed():
    t = TransferTuner()
    big = t.choose(0, "flush-d2h", BOOTSTRAP_BYTES, 1024, has_compute=False)
    assert big == BOOTSTRAP_CHUNKS
    small = t.choose(
        0, "flush-d2h", BOOTSTRAP_BYTES - 1, 1024, has_compute=False)
    assert small == 1


def test_tuner_chunked_run_teaches_lane_overhead():
    """A chunked wall above the overhead-free pipeline model raises the
    lane's learned per-chunk cost; a lane whose chunks are expensive
    talks itself back down to fewer chunks."""
    t = TransferTuner()
    _teach(t, u=10.0, c=10.0, d=10.0)
    before = t.lane_overhead_ms(0)
    many = t.choose(0, ("k",), 1 << 22, max_chunks=1024)
    assert many > 1
    # model says ~ peak + rest/c; report a wall WAY above it (slow rig)
    t.observe(0, ("k",), 1 << 22, 10.0, 0.0, 10.0, chunks=many,
              wall_ms=200.0)
    assert t.lane_overhead_ms(0) > before
    for _ in range(6):  # EMA converges onto the implied cost
        t.observe(0, ("k",), 1 << 22, 10.0, 0.0, 10.0, chunks=many,
                  wall_ms=200.0)
    assert t.choose(0, ("k",), 1 << 22, max_chunks=1024) < many


def test_tuner_chunked_wall_clamps_contaminated_estimates():
    """First contact is usually also first jit compile, so the measuring
    run's C carries compile time: the inflated peak flattens the model
    curve (the first choice degenerates to the largest candidate) and
    every implied overhead clamps at 0 against the oversized base, so
    over-chunking would freeze in place.  A chunked wall upper-bounds
    every phase (all of U, C, D happen inside it) — one honest streamed
    run must snap the estimates back to physics."""
    t = TransferTuner()
    # measuring run where compile landed in C (real phases ~ 5/5/5 ms)
    _teach(t, u=5.0, c=500.0, d=5.0)
    many = t.choose(0, ("k",), 1 << 22, max_chunks=1024)
    assert many > 1  # the contaminated model wants chunks
    # one honest chunked run: a 15 ms wall bounds every phase
    t.observe(0, ("k",), 1 << 22, 2.0, 0.0, 2.0, chunks=many, wall_ms=15.0)
    est = t.estimate(0, ("k",), 1 << 22)
    assert max(est) <= 15.0
    # ... which unblocks overhead learning: on a slow-chunk rig (walls
    # stuck at 50 ms regardless of count) the implied per-chunk cost is
    # now positive — against the un-clamped ~500 ms base it would clamp
    # at 0 forever — and the choice converges back to monolithic
    for _ in range(8):
        c = t.choose(0, ("k",), 1 << 22, max_chunks=1024)
        if c == 1:
            break
        t.observe(0, ("k",), 1 << 22, 2.0, 0.0, 2.0, chunks=c, wall_ms=50.0)
    assert t.choose(0, ("k",), 1 << 22, max_chunks=1024) == 1


def test_tuner_chunked_first_contact_stores_nothing():
    """A chunked run with no monolithic baseline cannot decompose its
    own wall — it must not seed the observation table."""
    t = TransferTuner()
    t.observe(0, ("k",), 1 << 22, 5.0, 1.0, 5.0, chunks=4, wall_ms=12.0)
    assert t.choose(0, ("k",), 1 << 22, max_chunks=1024) == 1  # still first contact


def test_tuner_bytes_bucket_quantization():
    """±quantization-step balancer moves stay in one bucket — the
    observation is not thrashed by a few-element range wiggle."""
    t = TransferTuner()
    assert t.bytes_bucket(1 << 20) == 1 << 20
    assert t.bytes_bucket((1 << 20) + 1) == 1 << 21
    _teach(t, nbytes=(1 << 20) + 5000, u=20.0, c=5.0, d=20.0)
    same_bucket = t.choose(0, ("k",), (1 << 20) + 9000, max_chunks=1024)
    assert same_bucket > 1  # hit the stored observation, not first contact


def test_tuner_candidates_respect_cap():
    t = TransferTuner()
    _teach(t, u=50.0, c=1.0, d=50.0)  # wants many chunks
    assert t.choose(0, ("k",), 1 << 22, max_chunks=3) <= 3
    assert t.choose(0, ("k",), 1 << 22, max_chunks=1024) in CHUNK_CANDIDATES


# ---------------------------------------------------------------------------
# acceptance pins: streamed == monolithic, element-exact
# ---------------------------------------------------------------------------

def test_streamed_bit_identical_mandelbrot_image(devs):
    """The acceptance gate, plain path: the chunked double-buffered
    wavefront produces a BIT-identical mandelbrot image (write-side
    streaming: per-chunk D2H issued behind the chunk's launch)."""
    from cekirdekler_tpu.workloads import MANDELBROT_SRC

    w = h = 256
    n = w * h
    vals = (-2.0, -1.25, 2.5 / w, 2.5 / h, w, 64)
    images = {}
    for streamed in (False, True):
        cr = NumberCruncher(devs.subset(2), MANDELBROT_SRC)
        cr.streamed_transfers = streamed
        cr.stream_chunks = 8 if streamed else 0  # pin: engage for sure
        out = ClArray(n, np.float32, name=f"s{streamed}", read=False,
                      write=True)
        for _ in range(3):
            out.compute(cr, 81, "mandelbrot", n, 256, values=vals)
        if streamed:
            assert any(
                c > 1 for c in cr.cores.last_stream_chunks.values()
            ), cr.cores.last_stream_chunks
        images[streamed] = np.asarray(out).copy()
        cr.dispose()
    np.testing.assert_array_equal(images[True], images[False])


@pytest.mark.parametrize("fused", [False, True])
def test_streamed_bit_identical_accumulating_nbody(devs, fused):
    """The acceptance gate, enqueue path × fused dispatch on AND off:
    accumulating n-body velocities (read-side chunk streaming of the
    partial-read velocity operands + chunked flush drain) are
    bit-identical to the monolithic path."""
    from cekirdekler_tpu.workloads import NBODY_SRC, _nbody_rig

    n, iters = 512, 8
    results = {}
    for streamed in (False, True):
        _, (x, y, z), vel = _nbody_rig(n, f"s{int(streamed)}f{int(fused)}")
        cr = NumberCruncher(devs.subset(2), NBODY_SRC)
        cr.fused_dispatch = fused
        cr.streamed_transfers = streamed
        cr.stream_chunks = 4 if streamed else 0
        g = x.next_param(y, z, *vel)
        cr.enqueue_mode = True
        for _ in range(iters):
            g.compute(cr, 82, "nBody", n, 64, values=(n, 1e-4))
        cr.enqueue_mode = False
        results[streamed] = [np.asarray(v).copy() for v in vel]
        cr.dispose()
    for a, b in zip(results[True], results[False]):
        np.testing.assert_array_equal(a, b)


def test_streamed_records_chunk_spans(devs):
    """The observability contract: a streamed phase emits upload-chunk /
    download-chunk spans (distinct kinds from the monolithic upload /
    download), and the chunk counters move."""
    from cekirdekler_tpu.metrics import REGISTRY
    from cekirdekler_tpu.trace.spans import TRACER

    src = """
    __kernel void tri(__global float* a, __global float* o) {
        int i = get_global_id(0);
        o[i] = a[i] * 3.0f;
    }"""
    n = 4096
    cr = NumberCruncher(devs.subset(1), src)
    cr.stream_chunks = 4
    a = ClArray(np.arange(n, dtype=np.float32), name="ta",
                partial_read=True, read_only=True)
    o = ClArray(n, np.float32, name="to", write_only=True)
    TRACER.enable(clear=True)
    try:
        a.next_param(o).compute(cr, 83, "tri", n, 64)
    finally:
        TRACER.disable()
    kinds = {s.kind for s in TRACER.snapshot()}
    assert "upload-chunk" in kinds and "download-chunk" in kinds, kinds
    chunk = {
        k: v for k, v in REGISTRY.snapshot()["counters"].items()
        if k.startswith("ck_stream_chunks_total")
    }
    assert any(v > 0 for v in chunk.values()), chunk
    np.testing.assert_array_equal(np.asarray(o), np.arange(n) * 3.0)
    cr.dispose()


def test_streamed_autotune_defaults_to_measuring_run_then_engages(devs):
    """Production default (stream_chunks=0): call 1 is the monolithic
    measuring run (chunks=1 recorded), a later call engages chunks once
    the model sees transfer worth hiding — and a forced re-partition
    resets the tuner (ck_stream_retune_total moves)."""
    src = """
    __kernel void cp(__global float* a, __global float* o) {
        int i = get_global_id(0);
        o[i] = a[i] + 1.0f;
    }"""
    n = 1 << 16
    cr = NumberCruncher(devs.subset(1), src)
    t = cr.transfer_tuner
    # a synthetic link seed makes transfers look expensive relative to
    # per-chunk overhead, so the second call must engage chunks (the
    # real link's weather would make this test flaky either way)
    t.seed_link(0, 50.0, 50.0)
    a = ClArray(np.zeros(n, np.float32), name="ca", partial_read=True,
                read_only=True)
    o = ClArray(n, np.float32, name="co", write_only=True)
    g = a.next_param(o)
    g.compute(cr, 84, "cp", n, 64)
    assert cr.cores.last_stream_chunks.get(0) == 1  # the measuring run
    # teach the model an expensive link for this key, cheap chunks
    t.observe(0, ("cp",), 8 * n, 40.0, 1.0, 40.0, chunks=1)
    g.compute(cr, 84, "cp", n, 64)
    assert cr.cores.last_stream_chunks.get(0, 1) > 1
    before = t.retunes
    t.on_repartition()
    assert t.retunes == before + 1
    g.compute(cr, 84, "cp", n, 64)  # back to a measuring run
    assert cr.cores.last_stream_chunks.get(0) == 1
    np.testing.assert_array_equal(np.asarray(o), 1.0)
    cr.dispose()


def test_tuner_key_matches_between_choose_and_observe(devs):
    """Regression: choose() and observe() must key the SAME byte count
    for one phase (phase.classify's key_bytes is the one formula).  A
    read+write partition array rides both the upload and the download
    wavefront (counted twice); a second formula that counted it once
    landed the measuring run's observation in a different power-of-two
    bucket than the lookup — every call was a "first contact" and the
    streamed path was silently dead for such workloads."""
    src = """
    __kernel void bump(__global float* a) {
        int i = get_global_id(0);
        a[i] = a[i] + 1.0f;
    }"""
    n = 1 << 14
    cr = NumberCruncher(devs.subset(1), src)
    t = cr.transfer_tuner
    a = ClArray(np.zeros(n, np.float32), name="rw", partial_read=True)
    a.compute(cr, 85, "bump", n, 64)  # the monolithic measuring run
    w = cr.cores.workers[0]
    expect = classify(cr.program, ("bump",), [a], w, 0, n, cut=True,
                      single=True, enqueue=False, owners={}).key_bytes
    assert expect == 2 * n * 4  # read AND write wavefronts
    kk = tuner_kernel_key(("bump",), ())
    assert list(t._obs) == [(0, kk, t.bytes_bucket(expect))]
    # dict-shaped value args key on sorted ITEMS — tuple(dict) keeps
    # only the names and would collapse a 100x value change (stale C
    # estimate, no re-measure) into one key
    k1 = tuner_kernel_key(("bump",), {"bump": (1000,)})
    k2 = tuner_kernel_key(("bump",), {"bump": (10,)})
    assert k1 != k2
    assert tuner_kernel_key(
        ("bump",), {"bump": np.zeros(4)}) == (("bump",), None)
    np.testing.assert_array_equal(np.asarray(a), 1.0)
    cr.dispose()


@pytest.mark.parametrize("engine", ["monolithic", "streamed", "driver",
                                    "event"])
def test_a_window_over_part_of_an_array_keeps_its_updates(devs, engine):
    """Residency is asked over the work items' own elements of a
    ``partial_read`` array, the same under every engine
    (``core/window.holds``).  One lane computing over a PART of an array
    in enqueue mode: asked over the whole array (as the streamed engine
    once did on one lane, where the monolithic one uploads whole), what
    the chunks covered never sufficed, every call uploaded the host's
    stale copy over the device's, and a window of three increments read
    back 1."""
    from cekirdekler_tpu.core.cores import PIPELINE_DRIVER, PIPELINE_EVENT

    src = """
    __kernel void inc(__global float* a) {
        int i = get_global_id(0);
        a[i] = a[i] + 1.0f;
    }"""
    how = {"driver": dict(pipeline=True, pipeline_blobs=4,
                          pipeline_type=PIPELINE_DRIVER),
           "event": dict(pipeline=True, pipeline_blobs=4,
                         pipeline_type=PIPELINE_EVENT)}.get(engine, {})
    cr = NumberCruncher(devs.subset(1), src)
    try:
        cr.stream_chunks = 4 if engine == "streamed" else 1
        cr.fused_dispatch = False  # every call takes the per-call path
        x = ClArray(np.zeros(2048, np.float32), name="px", partial_read=True)
        cr.enqueue_mode = True
        for _ in range(3):
            x.compute(cr, 87, "inc", 1024, 64, global_offset=512, **how)
        cr.enqueue_mode = False
        want = np.zeros(2048, np.float32)
        want[512:1536] = 3.0
        np.testing.assert_array_equal(x.host(), want)
        if engine == "streamed":
            assert cr.cores.last_stream_chunks == {0: 4}
    finally:
        cr.dispose()


def test_flush_drain_feeds_transfer_benchmarks(devs):
    """The enqueue flush drain attributes each (lane, cid)'s D2H wall
    into Worker.transfer_benchmarks — the feed that lets the balancer's
    transfer floor bind where steady-state enqueue benches carry no
    transfer term at all."""
    src = """
    __kernel void put(__global float* a) {
        int i = get_global_id(0);
        a[i] = a[i] + 2.0f;
    }"""
    n = 1 << 14
    cr = NumberCruncher(devs.subset(2), src)
    a = ClArray(np.zeros(n, np.float32), name="fa", partial_read=True)
    cr.enqueue_mode = True
    for _ in range(3):
        a.compute(cr, 86, "put", n, 64)
    # the drain normalizes by iterations since the last flush (the
    # enqueue benches it floors against are per-ITERATION) — the
    # counter must hold the window series' count here and clear after
    assert cr.cores._window.flush_iters.get(86) == 3
    cr.enqueue_mode = False  # flush: the drain runs here
    assert cr.cores._window.flush_iters == {}
    for w in cr.cores.workers[:2]:
        assert w.transfer_benchmarks.get(86, 0.0) > 0.0, (
            w.index, w.transfer_benchmarks)
    # regression: steady-state zero-transfer phases (uploads covered,
    # downloads deferred) must NOT clobber the drain's value — it is
    # the only honest link cost the next rebalance can floor against
    drained = {w.index: w.transfer_benchmarks[86]
               for w in cr.cores.workers[:2]}
    cr.enqueue_mode = True
    for _ in range(2):
        a.compute(cr, 86, "put", n, 64)
    for w in cr.cores.workers[:2]:
        assert w.transfer_benchmarks.get(86, 0.0) > 0.0, (
            "zero-transfer phase clobbered the drain value",
            w.index, drained[w.index], w.transfer_benchmarks)
    cr.enqueue_mode = False
    np.testing.assert_array_equal(np.asarray(a), 10.0)
    cr.dispose()
