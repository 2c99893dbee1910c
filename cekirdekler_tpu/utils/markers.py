"""Progress markers: the framework's dispatch-progress observability
primitive.

The reference's one real observability feature is queue markers — native
callbacks count how many enqueued markers a command queue has reached,
giving in-flight depth and a smoothed 'marker reach speed' used by the
pool scheduler for throttling (ClCommandQueue.cs:99-115,
ClNumberCruncher.cs:356-372, ClPipeline.cs:4788-4827).  The TPU analogue
counts dispatched vs retired operations per lane: XLA dispatch is async,
so 'reached' means the op's result became ready — :meth:`reach_when_ready`
joins ``block_until_ready`` on a completion thread, the PJRT-side
equivalent of the reference's queue-completion callback.

The added/reached counts live in the native C++ counter
(native/kutuphane_tpu.cpp ck_createMarkerCounter et al.) when the library
is available — the same native-callback-counter architecture as the
reference — with a pure-Python fallback.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque

from ..native import load as _load_native

__all__ = ["MarkerCounter"]


class MarkerCounter:
    """Dispatched/retired op counting + smoothed retire rate.

    ``add()`` marks a dispatch; ``reach()`` marks completion *now*;
    ``reach_when_ready(x)`` marks completion when the device value ``x``
    actually retires.  The rate estimate averages the last ``window``
    retire intervals (the reference's 15-sample markerReachSpeed smoothing,
    ClPipeline.cs:4788-4817).
    """

    def __init__(self, window: int = 15):
        self._lock = threading.Lock()
        # (retire-observation time, op count) — batched observations carry
        # their op count so reach_speed() stays ops/second
        self._times: deque[tuple[float, int]] = deque(maxlen=window)
        self._completions: "queue.Queue" = queue.Queue()
        self._completion_thread: threading.Thread | None = None
        self._closed = False
        self._native = _load_native()
        # python-side counters always exist: they are the fallback when no
        # native library is loaded AND the final snapshot after close()
        # releases the native counter (queries must keep working)
        self._added = 0
        self._reached = 0
        self._nid = (
            self._native.ck_createMarkerCounter()
            if self._native is not None else None
        )

    def close(self) -> None:
        """Stop the completion thread and release the native counter.
        ``_closed`` makes the drain thread skip further device joins, so
        the join below converges even when a burst of completions is
        queued on a slow link."""
        self._closed = True
        t = self._completion_thread
        if t is not None:
            self._completions.put(None)
            t.join(timeout=5.0)
            self._completion_thread = None
        # every native access (here and in the count paths) happens under
        # the lock: a reader racing this delete would otherwise pass a
        # freed counter id into the C library (use-after-free)
        with self._lock:
            if self._nid is not None and self._native is not None:
                # snapshot final counts so added/reached/remaining() keep
                # answering after the native counter is gone
                self._added = int(self._native.ck_markersAdded(self._nid))
                self._reached = int(self._native.ck_markersReached(self._nid))
                self._native.ck_deleteMarkerCounter(self._nid)
                self._nid = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    # -- counting ------------------------------------------------------------
    def add(self, n: int = 1) -> None:
        with self._lock:
            if self._nid is not None:
                for _ in range(n):
                    self._native.ck_addMarker(self._nid)
            else:
                self._added += n

    def reach(self, n: int = 1) -> None:
        now = time.perf_counter()
        with self._lock:
            if self._nid is not None:
                for _ in range(n):
                    self._native.ck_markerReached(self._nid)
            else:
                self._reached += n
            # (time, count) samples: batched retirement observations carry
            # their op count, so reach_speed() stays ops/second — n bunched
            # reach() calls would otherwise compress the window span and
            # inflate the rate by orders of magnitude
            self._times.append((now, n))

    def reach_when_ready(self, x, n: int = 1) -> None:
        """Reach when ``x`` (a jax.Array or any object with
        ``block_until_ready``) retires on the device — joined on a
        completion thread so in-flight depth reflects real device work,
        not host dispatch."""
        # ckcheck: ok double-checked lazy start — re-validated under _lock
        if self._completion_thread is None:
            with self._lock:
                if self._completion_thread is None and not self._closed:
                    # daemon: a hung device must not block interpreter exit
                    self._completion_thread = threading.Thread(
                        target=self._drain_completions,
                        name="marker-reach",
                        daemon=True,
                    )
                    self._completion_thread.start()
        self._completions.put((x, n))

    def _drain_completions(self) -> None:
        # BATCHED joins: when several completions are queued, they are
        # joined with ONE jax.block_until_ready over the whole batch (NOT
        # only the newest item — transfer and compute streams of one
        # device can retire out of order, so a single-item join would
        # under-prove the batch).  Without batching, a burst of light
        # dispatches pays one join per item, the thread lags behind,
        # remaining() overestimates in-flight depth, and close()'s
        # bounded join can leave an orphan thread to die inside PJRT
        # teardown at interpreter exit (native terminate).  The whole
        # batch retires as ONE weighted rate sample (see below).
        while True:
            # ckcheck: ok sentinel-terminated daemon loop — close()
            # always enqueues the None sentinel; the unbounded get is
            # this thread's idle state
            item = self._completions.get()
            if item is None:
                return
            batch = [item]
            while True:
                try:
                    nxt = self._completions.get_nowait()
                except queue.Empty:
                    break
                if nxt is None:  # close() requested: finish batch, exit
                    item = None
                    break
                batch.append(nxt)
            if not self._closed:
                try:
                    import jax

                    jax.block_until_ready([x for x, _ in batch])
                except Exception:
                    # one poisoned op must not retire the REST of the batch
                    # early (block_until_ready raises on the first failure
                    # before joining the others): join the rest one by one
                    for x, _ in batch:
                        try:
                            x.block_until_ready()
                        except Exception:
                            pass  # a failed op still retires its marker
            # ONE weighted rate sample for the whole batch: per-item
            # reach() calls would bunch the window into microseconds and
            # inflate reach_speed() by orders of magnitude
            self.reach(sum(n for _, n in batch))
            if item is None:
                return

    # -- queries -------------------------------------------------------------
    @property
    def added(self) -> int:
        with self._lock:
            if self._nid is not None:
                return int(self._native.ck_markersAdded(self._nid))
            return self._added

    @property
    def reached(self) -> int:
        with self._lock:
            if self._nid is not None:
                return int(self._native.ck_markersReached(self._nid))
            return self._reached

    def remaining(self) -> int:
        """In-flight depth (reference: countMarkersRemaining)."""
        with self._lock:
            if self._nid is not None:
                return int(self._native.ck_markersRemaining(self._nid))
            return self._added - self._reached

    def drain(self, timeout: float = 30.0) -> None:
        """Block until every added marker has reached (bounded)."""
        deadline = time.perf_counter() + timeout
        while self.remaining() > 0 and time.perf_counter() < deadline:
            time.sleep(0.0005)

    def reach_speed(self) -> float:
        """Retired ops/second over the smoothing window (0 if <2 samples):
        ops counted from the second observation on, over the window span —
        each sample may represent a batch of retirements."""
        with self._lock:
            if len(self._times) < 2:
                return 0.0
            span = self._times[-1][0] - self._times[0][0]
            ops = sum(n for _, n in list(self._times)[1:])
            return ops / span if span > 0 else 0.0

    def reset(self) -> None:
        with self._lock:
            if self._nid is not None:
                self._native.ck_resetMarkerCounter(self._nid)
            self._added = 0
            self._reached = 0
            self._times.clear()
