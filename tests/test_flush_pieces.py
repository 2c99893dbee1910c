"""The flush's batched read-back (``Worker.download_slices_async``, issued by
``Sync.start_deferred_downloads`` through ``Cores``' entry of the name): ONE dispatch a lane cuts every deferred
record of the lane into pieces of one fixed length, whatever the split.

Held here, on the CPU rig: the host arrays come out bit-identical to the
per-record path this replaced (kept below as the oracle, built from the
entries that path used and that stay: ``download_async`` /
``download_chunk_async``), for overlapping records of two lanes, a
``write_all`` record among sliced ones, sizes that are no whole number of
pieces, several elements a work item, and ``flush()`` from a second host
thread in the middle of a window; a split never seen compiles nothing; the
issue carries one ``part:lane`` mark a lane with ``dispatches == 1``."""

import threading
import time

import numpy as np
import pytest

from cekirdekler_tpu import ClArray
from cekirdekler_tpu.core import NumberCruncher
from cekirdekler_tpu.core import worker as worker_mod
from cekirdekler_tpu.core.cores import Cores
from cekirdekler_tpu.core.stream import chunk_plan
from cekirdekler_tpu.core.sync import latest_records
from cekirdekler_tpu.hardware import platforms
from cekirdekler_tpu.trace.spans import TRACER

INC = """
__kernel void inc(__global float* a) {
    int i = get_global_id(0);
    a[i] = a[i] + 1.0f;
}
"""
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
POISON = -7.0


@pytest.fixture(scope="module")
def devs():
    return platforms().cpus()


def per_record_start(chunks: int):
    """The path before the batched entry, as the oracle: one ``_slice_out``
    a chunk of a record, the record's size a static argument."""

    def start(self, pending, lock_each):
        handles = []
        for _, w, p, offset, size, write_all, cid in latest_records(
                pending):
            epw = p.flags.elements_per_work_item
            if write_all:
                handles.append((w.download_async(p, 0, p.size, True), w, cid))
            elif chunks > 1 and size > 1:
                handles += [
                    (w.download_chunk_async(p, (offset + coff) * epw,
                                            csz * epw), w, cid)
                    for coff, csz in chunk_plan(size, 1, chunks)]
            else:
                handles.append((w.download_async(
                    p, offset * epw, size * epw, False), w, cid))
        return handles

    return start


class Rig:
    """Lanes whose buffers of ``arrays`` hold a content of their own
    (``lane * 1e5 + element``), so that a host element says which lane's
    record wrote it last."""

    def __init__(self, devs, lanes: int, arrays: dict):
        self.cr = NumberCruncher(devs.subset(lanes), INC)
        self.cores = self.cr.cores
        self.arrays = arrays
        self.on_lane = {}
        import jax

        for w in self.cores.workers:
            for name, arr in arrays.items():
                content = (w.index * 1e5
                           + np.arange(arr.size)).astype(arr.host().dtype)
                self.on_lane[w.index, name] = content
                w.set_buffer(arr, jax.device_put(content, w.device))

    def pending(self, rows):
        """``(seq, lane, array, offset, size, write_all)`` -> the records
        ``Cores`` keeps (offset and size in work items)."""
        return [(seq, self.cores.workers[lane], self.arrays[name], off, size,
                 write_all, 9) for seq, lane, name, off, size, write_all
                in rows]

    def expected(self, rows) -> dict:
        """What the records leave in host arrays poisoned beforehand: the
        newest record a (lane, array), in chronological order."""
        out = {k: np.full(a.size, POISON, a.host().dtype)
               for k, a in self.arrays.items()}
        newest = {}
        for row in rows:
            key = (row[1], row[2])
            if key not in newest or row[0] > newest[key][0]:
                newest[key] = row
        for seq, lane, name, off, size, write_all in sorted(newest.values()):
            epw = self.arrays[name].flags.elements_per_work_item
            a, b = ((0, self.arrays[name].size) if write_all
                    else (off * epw, (off + size) * epw))
            out[name][a:b] = self.on_lane[lane, name][a:b]
        return out

    def flush(self, rows, lock_each: bool) -> dict:
        for arr in self.arrays.values():
            arr.host()[:] = POISON
        entries = self.cores._start_deferred_downloads(
            self.pending(rows), lock_each)
        self.cores._sync.finish_deferred(entries, {})
        return {k: a.host().copy() for k, a in self.arrays.items()}


def _arrays(n=4096):
    return {
        "x": ClArray(np.zeros(n, np.float32), name="fp_x", partial_read=True),
        "all": ClArray(np.zeros(300, np.float32), name="fp_all",
                       write_all=True),
        "pair": ClArray(np.zeros(2 * n, np.int32), name="fp_pair",
                        partial_read=True, elements_per_work_item=2),
    }


#: name -> records ``(seq, lane, array, offset, size, write_all)``
CASES = {
    # the grown lane recomputed what the shrunk lane wrote earlier
    "overlap-newer-is-lane-1": [
        (3, 0, "x", 0, 2304, False), (5, 1, "x", 1792, 2304, False)],
    "overlap-newer-is-lane-0": [
        (6, 0, "x", 0, 2304, False), (5, 1, "x", 1792, 2304, False)],
    "older-record-of-a-lane-is-dropped": [
        (1, 0, "x", 0, 4096, False), (4, 0, "x", 64, 1024, False),
        (2, 1, "x", 2048, 2048, False)],
    "write-all-among-sliced": [
        (1, 0, "x", 0, 2048, False), (2, 1, "all", 0, 300, True),
        (3, 1, "x", 2048, 2048, False), (4, 0, "pair", 0, 1000, False),
        (5, 1, "pair", 1000, 3096, False)],
    # no whole number of pieces, a last piece that starts before its
    # window, a record of one element, a record at the buffer's very end
    "ragged": [
        (1, 0, "x", 37, 1001, False), (2, 1, "x", 4096 - 300, 300, False),
        (3, 2, "x", 1038, 1, False), (4, 2, "pair", 4095, 1, False),
        (5, 0, "pair", 1, 777, False)],
    "three-lanes-tiling": [
        (1, 0, "x", 0, 1408, False), (2, 1, "x", 1408, 1280, False),
        (3, 2, "x", 2688, 1408, False)],
}


@pytest.mark.parametrize("lock_each", [False, True], ids=["atomic", "flush"])
@pytest.mark.parametrize("piece_bytes", [1024, 4 << 20],
                         ids=["pieces-of-1KiB", "one-piece"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_batched_flush_equals_the_per_record_path(devs, monkeypatch, case,
                                                  piece_bytes, lock_each):
    rows = CASES[case]
    rig = Rig(devs, 3, _arrays())
    try:
        monkeypatch.setattr(worker_mod, "PIECE_BYTES", piece_bytes)
        got = rig.flush(rows, lock_each)
        with monkeypatch.context() as m:
            m.setattr(Cores, "_start_deferred_downloads", per_record_start(4))
            oracle = rig.flush(rows, lock_each)
        want = rig.expected(rows)
    finally:
        rig.cr.dispose()
    for name in want:
        np.testing.assert_array_equal(got[name], oracle[name], err_msg=name)
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


def test_pieces_are_cut_by_one_dispatch_a_lane_and_only_the_needed_cross(
        devs, monkeypatch):
    """Two arrays of a lane ride ONE program; the pieces beyond a share
    (the count is a power of two) stay on the device."""
    monkeypatch.setattr(worker_mod, "PIECE_BYTES", 1024)  # 256 floats
    rig = Rig(devs, 2, _arrays())
    calls = []
    real = worker_mod._slice_pieces
    monkeypatch.setattr(
        worker_mod, "_slice_pieces",
        lambda bufs, offs, counts, lengths: calls.append(
            (len(bufs), list(offs), counts, lengths))
        or real(bufs, offs, counts, lengths))
    try:
        w = rig.cores.workers[1]
        x, pair = rig.arrays["x"], rig.arrays["pair"]
        # 1100 floats: 5 pieces of 256, cut out of 8; 70 ints: one of 256
        got, dispatches = w.download_slices_async(
            [(x, 100, 1100, False), (pair, 8, 70, False)])
    finally:
        rig.cr.dispose()
    assert dispatches == 1 and len(calls) == 1
    n_bufs, offs, counts, lengths = calls[0]
    assert (n_bufs, counts, lengths) == (2, (8, 1), (256, 256))
    assert offs[:5] == [100, 356, 612, 868, 1124] and len(offs) == 9
    assert [len(hs) for hs in got] == [5, 1]
    assert sum(h[1].nbytes for hs in got for h in hs) == 6 * 256 * 4
    # every handle names its record's own elements: where they go in the
    # host array, and which of the piece's they are
    assert [(h[2], h[9], h[10]) for h in got[0]] == [
        (100, 0, 256), (356, 0, 256), (612, 0, 256), (868, 0, 256),
        (1124, 0, 76)]
    assert [(h[2], h[9], h[10]) for h in got[1]] == [(8, 0, 70)]
    assert {h[6] for h in got[0]} == {"download-chunk"}
    assert got[1][0][6] == "download"


class CompileCounter:
    def __init__(self):
        from jax import monitoring

        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == COMPILE_EVENT:
            self.n += 1


def _tiling(n, cuts):
    """Records of lanes tiling ``[0, n)`` at ``cuts``."""
    edges = [0, *cuts, n]
    return [(seq + 1, lane, "x", a, b - a, False)
            for seq, (lane, (a, b)) in enumerate(enumerate(
                zip(edges, edges[1:])))]


@pytest.mark.parametrize("lock_each", [False, True], ids=["atomic", "flush"])
def test_a_split_never_seen_compiles_nothing(devs, monkeypatch, lock_each):
    """After a warm-up over a fixed set of splits, a split the balancer
    never chose before (no lane's share above the largest it has had, to
    the next power of two of pieces) finds its executables: no backend
    compile in the flush, no new entry of the slicing program."""
    monkeypatch.setattr(worker_mod, "PIECE_BYTES", 1024)
    # a length of this case's own: the entries it counts are its
    n = 6144 if lock_each else 5120
    rig = Rig(devs, 3, {"x": ClArray(np.zeros(n, np.float32),
                                     name="ns_x", partial_read=True)})
    counter = CompileCounter()
    try:
        for cuts in ([1408, 2688], [1216, 2944], [1024, 2048]):
            rig.flush(_tiling(n, cuts), lock_each)
        entries, compiles = worker_mod._slice_pieces._cache_size(), counter.n
        for cuts in ([1344, 2752], [1989, 2011 + 1000], [1537, 2687]):
            rows = _tiling(n, cuts)
            got = rig.flush(rows, lock_each)
            np.testing.assert_array_equal(got["x"], rig.expected(rows)["x"])
        assert counter.n == compiles
        assert worker_mod._slice_pieces._cache_size() == entries
        # a share that outgrows every count so far is what compiles: once
        rig.flush(_tiling(n, [4200, 4700]), lock_each)
        assert worker_mod._slice_pieces._cache_size() == entries + 1
    finally:
        rig.cr.dispose()


def _laggy(orig, secs=0.15):
    def f():
        time.sleep(secs)
        orig()

    return f


def _windows_with_moving_ranges(cr, x, n, windows, per_window, between=None):
    """Enqueue windows on two lanes, each lane lagging in turn so that the
    next window's first compute moves the ranges and flushes."""
    w0, w1 = cr.cores.workers
    f0, f1 = w0.fence, w1.fence
    moved = 0
    try:
        cr.enqueue_mode = True
        for win in range(windows):
            slow, orig = (w0, f0) if win % 2 == 0 else (w1, f1)
            slow.fence = _laggy(orig)
            before = cr.ranges_of(31)
            for _ in range(per_window):
                x.compute(cr, 31, "inc", n, 64)
                if between is not None:
                    between()
            moved += before is not None and cr.ranges_of(31) != before
            cr.barrier()
            w0.fence, w1.fence = f0, f1
        cr.enqueue_mode = False
    finally:
        w0.fence, w1.fence = f0, f1
    return moved


def test_the_issue_holds_one_lane_mark_a_lane_with_one_dispatch(
        devs, monkeypatch):
    """Inside ``part:issue`` of a range move's ``ck/resync``: one
    ``part:lane`` instant a lane, ``dispatches == 1``, with the pieces and
    bytes the lane was handed, before ``part:join``."""
    seen = []
    real = TRACER.instant

    def spy(kind, cid=None, lane=None, tag=None, **meta):
        if kind == "resync":
            seen.append((tag, lane, meta))
        return real(kind, cid=cid, lane=lane, tag=tag, **meta)

    n = 4096
    cr = NumberCruncher(devs.subset(2), INC)
    x = ClArray(np.zeros(n, np.float32), name="lm_x", partial_read=True)
    TRACER.enable(clear=True)
    monkeypatch.setattr(TRACER, "instant", spy)
    try:
        moved = _windows_with_moving_ranges(cr, x, n, 4, 3)
    finally:
        TRACER.disable()
        cr.dispose()
    assert moved >= 1
    np.testing.assert_array_equal(np.asarray(x), 12.0)
    tags = [t for t, _l, _m in seen]
    starts = [i for i, t in enumerate(tags) if t == "part:issue"]
    assert len(starts) >= moved  # the range moves, and the last flush
    for i in starts:
        stretch = seen[i + 1: tags.index("part:join", i)]
        assert [t for t, _l, _m in stretch] == ["part:lane"] * len(stretch)
        assert sorted(lane for _t, lane, _m in stretch) == [0, 1]
        for _t, _lane, meta in stretch:
            assert meta["dispatches"] == 1 and meta["pieces"] == 1
            assert meta["bytes"] > 0 and meta["issue_us"] >= 0.0


@pytest.mark.parametrize("path", ["batched", "per-record"])
def test_flush_from_a_second_host_thread_mid_window(devs, monkeypatch, path):
    """One thread drives windows whose ranges move at every barrier; after
    each of its computes a second thread runs ``flush()`` (the
    ``lock_each`` form of the same issue: it takes each lane's lock on the
    thread that issues) while the window is still open.  The adds are
    exact, on the batched path as on the per-record path it replaced."""
    monkeypatch.setattr(worker_mod, "PIECE_BYTES", 2048)
    if path == "per-record":
        monkeypatch.setattr(Cores, "_start_deferred_downloads",
                            per_record_start(4))
    n, windows, per_window = 4096, 6, 3
    cr = NumberCruncher(devs.subset(2), INC)
    x = ClArray(np.zeros(n, np.float32), name="mw_x", partial_read=True)
    go, done, stop = threading.Event(), threading.Event(), threading.Event()
    errors, seen = [], []

    def flusher():
        while True:
            go.wait()
            go.clear()
            if stop.is_set():
                return
            try:
                cr.flush()
                seen.append(x.host().copy())
            except Exception as e:  # pragma: no cover - failure path
                errors.append(e)
            done.set()

    def hand_over():
        go.set()
        assert done.wait(timeout=60.0)
        done.clear()

    t = threading.Thread(target=flusher)
    t.start()
    try:
        moved = _windows_with_moving_ranges(
            cr, x, n, windows, per_window, between=hand_over)
    finally:
        stop.set()
        go.set()
        t.join(timeout=60.0)
        cr.dispose()
    assert not errors, errors
    assert moved >= 1 and len(seen) == windows * per_window
    # every flush left the host at the count of computes so far
    for k, host in enumerate(seen):
        np.testing.assert_array_equal(host, float(k + 1))
    np.testing.assert_array_equal(np.asarray(x), float(windows * per_window))
