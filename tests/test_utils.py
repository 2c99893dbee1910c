"""Aux subsystem tests: checkpoint/resume (atomic, sharded pytrees),
marker counters, perf history."""

import os
import time as _time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cekirdekler_tpu as ct
from cekirdekler_tpu.arrays.clarray import ClArray
from cekirdekler_tpu.core.cruncher import NumberCruncher
from cekirdekler_tpu.utils.checkpoint import (
    latest_step,
    load_arrays,
    load_pytree,
    save_arrays,
    save_pytree,
)
from cekirdekler_tpu.utils.markers import MarkerCounter


def _cpus(n=2):
    return ct.all_devices().cpus().subset(n)


# -- checkpoint --------------------------------------------------------------

def test_array_checkpoint_roundtrip(tmp_path):
    root = str(tmp_path / "ck")
    a = ClArray(np.arange(100, dtype=np.float32))
    save_arrays(root, 5, {"a": a, "b": np.ones(3)})
    save_arrays(root, 9, {"a": a, "b": np.zeros(3)})
    assert latest_step(root) == 9
    got = load_arrays(root)  # latest
    np.testing.assert_array_equal(got["a"], a.host())
    np.testing.assert_array_equal(got["b"], np.zeros(3))
    got5 = load_arrays(root, 5)
    np.testing.assert_array_equal(got5["b"], np.ones(3))


def test_pytree_checkpoint_roundtrip_with_sharding(tmp_path):
    from cekirdekler_tpu import parallel as par
    from cekirdekler_tpu.models import Transformer, TransformerConfig

    root = str(tmp_path / "ck")
    cfg = TransformerConfig(vocab=32, d_model=16, n_layers=1, n_heads=2,
                            d_ff=32, dtype=jnp.float32)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = par.make_mesh(jax.devices("cpu")[:4], dp=2, tp=2)
    sharded = model.shard_params(params, mesh)
    save_pytree(root, 100, sharded)

    fresh = model.shard_params(model.init(jax.random.PRNGKey(1)), mesh)
    restored = load_pytree(
        root, fresh, sharding_fn=lambda l, x: jax.device_put(x, l.sharding)
    )
    for a, b in zip(jax.tree_util.tree_leaves(sharded),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert b.sharding == a.sharding


def test_checkpoint_atomicity_no_partial_dirs(tmp_path):
    root = str(tmp_path / "ck")
    save_arrays(root, 1, {"x": np.ones(4)})
    leftovers = [d for d in os.listdir(root) if d.startswith(".ckpt_tmp_")]
    assert leftovers == []


def test_load_missing_checkpoint_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        load_arrays(str(tmp_path / "none"))


# -- markers -----------------------------------------------------------------

def test_marker_counter_basics():
    m = MarkerCounter(window=4)
    m.add(3)
    assert m.remaining() == 3
    m.reach()
    m.reach()
    assert m.reached == 2 and m.remaining() == 1
    m.reach()
    assert m.reach_speed() >= 0.0
    m.reset()
    assert m.added == 0 and m.remaining() == 0


def test_fine_grained_queue_control_counts_ops():
    n = 256
    a = ClArray(np.zeros(n, np.float32))
    cr = NumberCruncher(
        _cpus(2),
        "__kernel void f(__global float* a){ int i=get_global_id(0); a[i]+=1.0f; }",
    )
    try:
        cr.fine_grained_queue_control = True
        a.compute(cr, 1, "f", n, 64)
        assert cr.count_markers_reached() > 0
        # compute() is synchronous, but "reached" is observed by the
        # marker counter's COMPLETION THREAD (reach_when_ready joins on
        # a daemon thread by design) — give the drain a bounded window
        # before asserting in-flight depth hit zero, else a loaded rig
        # races the thread and flakes
        deadline = _time.time() + 5.0
        while cr.count_markers_remaining() and _time.time() < deadline:
            _time.sleep(0.01)
        assert cr.count_markers_remaining() == 0
        cr.fine_grained_queue_control = False
        assert not cr.fine_grained_queue_control
    finally:
        cr.dispose()


# -- perf history ------------------------------------------------------------

def test_performance_history_accumulates():
    n = 256
    a = ClArray(np.zeros(n, np.float32))
    cr = NumberCruncher(
        _cpus(2),
        "__kernel void f(__global float* a){ int i=get_global_id(0); a[i]+=1.0f; }",
    )
    try:
        for _ in range(4):
            a.compute(cr, 7, "f", n, 64)
        hist = cr.performance_history(7)
        assert len(hist) == 4
        assert all(p.compute_id == 7 for p in hist)
        assert sum(hist[-1].device_items) == n
    finally:
        cr.dispose()


def test_timeline_merged_busy_math():
    from cekirdekler_tpu.utils.timeline import _merged_busy

    # disjoint + overlapping + contained intervals
    assert _merged_busy([(0.0, 10.0), (20.0, 30.0)]) == 20.0
    assert _merged_busy([(0.0, 10.0), (5.0, 15.0)]) == 15.0
    assert _merged_busy([(0.0, 10.0), (2.0, 3.0)]) == 10.0
    assert _merged_busy([]) == 0.0


def test_timeline_capture_graceful_without_device_events(tmp_path):
    """On the CPU rig the profiler exposes no '/device:' process — the
    capture must still run the region and return an empty analysis (the
    TPU path, where zero device events is a FAILURE, is chip_smoke.py's
    stage 6)."""
    import jax.numpy as jnp
    import numpy as np

    from cekirdekler_tpu.utils import timeline

    with timeline.capture(str(tmp_path / "tr")) as result:
        x = jnp.arange(1024, dtype=jnp.float32) * 2
        np.asarray(x)
    tl = result()
    assert tl.span_ms >= 0.0
    assert 0.0 <= tl.compute_busy_fraction <= 1.0 or tl.n_events == 0


def test_tracer_report_runs(tmp_path):
    import jax.numpy as jnp
    import numpy as np

    from cekirdekler_tpu.utils.timeline import Tracer

    tr = Tracer(str(tmp_path / "traces"))
    with tr.region("warm"):
        np.asarray(jnp.ones(64) + 1)
    assert "warm" in tr.report()


def test_timeline_capture_propagates_region_exception(tmp_path):
    """An exception raised inside the traced region must surface unchanged
    (regression: the generator used to yield a second time, masking the
    real error as RuntimeError)."""
    import pytest

    from cekirdekler_tpu.utils import timeline

    with pytest.raises(ValueError, match="real error"):
        with timeline.capture(str(tmp_path / "tr")):
            raise ValueError("real error")


def test_user_event_counter_semantics():
    """ClUserEvent parity: fires on explicit trigger OR when the pending
    counter decrements to zero; waiters release (native path when the
    toolchain is present, threading fallback otherwise)."""
    from cekirdekler_tpu.utils.events import UserEvent

    ev = UserEvent()
    assert not ev.fired()
    ev.increment()
    ev.increment()
    assert ev.pending() == 2
    ev.decrement()
    assert not ev.fired()
    ev.decrement()
    assert ev.fired()
    assert ev.wait(timeout=1.0)
    ev.close()

    ev2 = UserEvent()
    assert not ev2.wait(timeout=0.05)  # times out untriggered
    ev2.trigger()
    assert ev2.wait(timeout=1.0)
    ev2.close()


def test_native_copy_engine_async_and_parallel():
    import numpy as np

    from cekirdekler_tpu import native
    from cekirdekler_tpu.utils.events import UserEvent

    lib = native.load()
    if lib is None:
        import pytest

        pytest.skip("native toolchain unavailable")
    src = np.arange(1 << 21, dtype=np.float32)  # 8 MiB
    dst = np.zeros_like(src)
    ev = UserEvent()
    lib.ck_copyAsync(dst.ctypes.data, src.ctypes.data, src.nbytes, ev._id)
    assert ev.wait(timeout=5.0)
    np.testing.assert_array_equal(dst, src)
    dst2 = np.zeros_like(src)
    lib.ck_copyParallel(dst2.ctypes.data, src.ctypes.data, src.nbytes, 4)
    np.testing.assert_array_equal(dst2, src)
    ev.close()


def test_marker_counter_concurrent_stress_and_close_races():
    """The drain thread's batching/close discipline under stress: many
    producers enqueue completion joins while another thread closes the
    counter mid-flight — no deadlock, no lost counts before close, clean
    repeated close()."""
    import threading
    import jax.numpy as jnp

    from cekirdekler_tpu.utils.markers import MarkerCounter

    for round_ in range(5):
        mc = MarkerCounter()
        xs = [jnp.zeros(4) + i for i in range(8)]
        race_close = round_ % 2 == 1  # odd rounds: close WHILE producing

        def producer(k):
            for i in range(25):
                try:
                    mc.add()
                    mc.reach_when_ready(xs[(k + i) % len(xs)])
                except Exception:
                    if not race_close:
                        raise  # only a racing close may interrupt

        threads = [threading.Thread(target=producer, args=(k,)) for k in range(4)]
        for t in threads:
            t.start()
        if race_close:
            mc.close()  # concurrent with live producers: no crash, no UAF
        for t in threads:
            t.join()
        if not race_close:
            mc.drain(timeout=20.0)
            assert mc.added == 100
            assert mc.remaining() == 0, mc.remaining()
            assert mc.reach_speed() >= 0.0
        # queries after close must keep answering (snapshot semantics)
        mc.close()
        mc.close()  # idempotent
        assert mc.added >= 0 and mc.reached >= 0 and mc.remaining() >= 0


def test_marker_counter_close_with_pending_completions():
    """close() while completions are still queued must return promptly
    (bounded join) and not crash at interpreter teardown — the r4 bug was
    an orphan drain thread dying inside PJRT teardown."""
    import jax.numpy as jnp

    from cekirdekler_tpu.utils.markers import MarkerCounter

    mc = MarkerCounter()
    x = jnp.zeros(16)
    for i in range(200):
        mc.add()
        mc.reach_when_ready(x + i)
    mc.close()  # must not hang on 200 queued joins
    assert mc.remaining() >= 0  # counts consistent, no exception
