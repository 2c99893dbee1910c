"""What ends an enqueue window: ``flush()`` (the deferred readbacks), the
flush-and-reset of a range move, and ``barrier()`` (reference:
flushLastUsedCommandQueue / finish, Worker.cs:364-423).
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import ExitStack, nullcontext
from typing import Callable

from ..metrics.registry import REGISTRY
from ..obs.decisions import DECISIONS
from ..obs.drain import DrainController
from ..obs.flight import FLIGHT, record_crash
from ..obs.health import HealthMonitor
from ..trace.attribution import split_fence_benches
from ..trace.spans import TRACER
from ..utils.faultinject import FAULTS
from .balance import per_iteration_benches
from .window import Window
from .worker import Worker

__all__ = ["Sync", "latest_records"]


def latest_records(pending) -> list[tuple]:
    """Most recent record per (worker, array), in CHRONOLOGICAL order
    (by sequence tag): after a sync-point rebalance two workers'
    latest slices of one array can overlap (the grown chip recomputed
    a region the shrunk chip wrote earlier) — the newer record must
    be the one that sticks on the host."""
    latest: dict[tuple[int, int], tuple] = {}
    for rec in pending:
        key = (id(rec[1]), id(rec[2]))
        cur = latest.get(key)
        if cur is None or rec[0] > cur[0]:
            latest[key] = rec
    return sorted(latest.values())


class Sync:
    """The sync points of one scheduler.  ``ranges`` is its range table
    (read only), ``lane_config`` its postmortem lane block.  ``start`` —
    the issue of the deferred downloads — is handed in per call: the
    scheduler's ``_start_deferred_downloads`` entry, which comes back to
    :meth:`start_deferred_downloads`."""

    def __init__(self, settings, workers: list[Worker],
                 pool: ThreadPoolExecutor, window: Window,
                 health: HealthMonitor, drain: DrainController,
                 ranges: dict, lane_config: Callable):
        self.settings = settings
        self.workers = workers
        self.pool = pool
        self.window = window
        self.health = health
        self.drain = drain
        self.ranges = ranges
        self._lane_config = lane_config
        # cached handle: the barrier is every window's fence — a registry
        # get-or-create per window is window_fence residue (r7 attribution)
        self._m_barriers = REGISTRY.counter(
            "ck_barriers_total", "enqueue-window sync points")

    def start_deferred_downloads(self, pending, lock_each: bool) -> list:
        """Start async downloads for the newest record per (worker,
        array) — ONE code path for flush() (which takes each worker's
        phase lock around that lane's issue: another host thread's lane
        may be mid-phase replacing buffer entries) and the atomic
        rebalance flush (whose caller already holds every worker
        lock).  Every slice is known before the first is issued, so a
        lane's share goes out at once: ONE dispatch a lane cuts all its
        records into pieces of one fixed length
        (``Worker.download_slices_async``), whatever the split — no
        executable is keyed on a range the balancer chose.  The pieces
        are the streamed drain's chunks: a piece's host memcpy
        (finish_download) overlaps the NEXT pieces' still-in-flight D2H
        instead of the whole fence draining at once.  The lanes go one
        after another on the caller's thread (from the lanes' own
        threads the four issues overlap and the join waits that much
        longer for the same bytes: PERF.md s.6, PR 42).  Returns
        ``(handle, worker, cid)`` entries for :meth:`finish_deferred`
        in the records' CHRONOLOGICAL order, whatever the order of
        issue: finish order is the order of the host writes."""
        records = latest_records(pending)
        by_lane: dict[Worker, list[int]] = {}
        for at, rec in enumerate(records):
            by_lane.setdefault(rec[1], []).append(at)
        handles: list[list] = [[] for _ in records]
        marks = []
        for w, mine in by_lane.items():
            t0 = time.perf_counter()
            with (w.lock if lock_each else nullcontext()):
                got, dispatches = w.download_slices_async([
                    (p, offset * p.flags.elements_per_work_item,
                     size * p.flags.elements_per_work_item, write_all)
                    for _, _, p, offset, size, write_all, _ in (
                        records[at] for at in mine)])
            for at, hs in zip(mine, got):
                handles[at] = hs
            marks.append((w.index, dispatches, [h for hs in got for h in hs],
                          time.perf_counter() - t0))
        if TRACER.active():
            # one mark a lane, where the issue ends (just before
            # ``part:join``, so that ``issue`` stays one stretch of the
            # caller's thread): what the lane was handed
            for lane, dispatches, issued, issue_s in sorted(
                    marks, key=lambda m: m[0]):
                TRACER.instant("resync", lane=lane, tag="part:lane",
                               dispatches=dispatches, pieces=len(issued),
                               bytes=sum(h[1].nbytes for h in issued),
                               issue_us=round(issue_s * 1e6, 1))
        return [(h, rec[1], rec[6])
                for rec, hs in zip(records, handles) for h in hs]

    def finish_deferred(self, entries, iters: dict[int, int]) -> None:
        """Join the flush's D2H handles in issue order, timing each
        (lane, cid)'s share of the drain into
        ``Worker.transfer_benchmarks`` — the integrated site where the
        balancer's transfer floor can BIND: in steady enqueue state a
        lane's in-window bench excludes transfers entirely (uploads
        covered, downloads deferred to here), so a slow effective link
        shows up only in this drain.  The drain is divided by the cid's
        iterations since the last flush (``iters``) because the enqueue
        benches the floor compares against are per-ITERATION
        (balance.per_iteration_benches) — feeding the raw per-flush
        total would over-floor every lane by the window count and snap
        converged shares back toward equal.  Attribution is approximate
        — the finish that waits absorbs shared-link contention — but it
        is a measured per-lane link cost where the compute bench has
        none."""
        acc: dict[tuple[Worker, int], float] = {}
        for h, w, cid in entries:
            t0 = time.perf_counter()
            Worker.finish_download(h)
            acc[(w, cid)] = acc.get((w, cid), 0.0) + (
                time.perf_counter() - t0
            )
        for (w, cid), s in acc.items():
            per_iter_s = s / max(1, iters.get(cid, 1))
            # under the worker lock (RLock — the atomic rebalance flush
            # already holds it): flush() runs on the caller thread with
            # no worker lock, so this store raced a concurrent enqueue
            # thread's in-phase transfer feed (ckcheck lockset finding)
            with w.lock:
                w.transfer_benchmarks[cid] = per_iter_s * 1000.0
            # lane health rides the same per-iteration normalization the
            # balancer floor uses, so windows of different sizes feed
            # one scale (a 4x-bigger window is not a 4x-slower link)
            if per_iter_s > 0.0:
                self.health.observe(w.index, "transfer", per_iter_s)

    def flush(self, start: Callable) -> None:
        """Read back and join everything deferred by enqueue mode.  Any
        open fused window is dispatched and drained first — the download
        slices must see the post-ladder buffers."""
        # (held: a window opened between the close and the take would
        # leave its records to this read-back and launch after it)
        self.window.close(hold=True)
        _tr = TRACER.t0("resync")
        try:
            pending, flush_iters = self.window.take_deferred()
        finally:
            self.window.release()
        TRACER.instant("resync", tag="part:issue")
        entries = start(pending, lock_each=True)
        TRACER.instant("resync", tag="part:join")
        self.finish_deferred(entries, flush_iters)
        TRACER.record("resync", _tr, tag="flush")

    def flush_and_reset_coverage(self, start: Callable) -> None:
        """The sync-point-rebalance flush: read back every deferred record
        AND reset every chip's upload coverage as ONE atomic step under
        ALL worker locks (the window-scoped coverage epoch).

        Why atomicity matters: with several host threads enqueuing
        different cids, a plain flush-then-reset lets another thread's
        window launch between the flush's host writes and the coverage
        reset — that thread's next covered-range check then re-uploads a
        host copy missing its own just-launched increments.  Holding every worker
        lock across [collect → download → host write → reset] makes the
        interleaving structurally impossible: any launch sequenced before
        the block has its record collected here (records are appended
        under the worker lock), and any launch after the block sees reset
        coverage AND a host already made current.  Each reset bumps
        Worker.coverage_epoch, which in-flight fused windows check per
        deferral (``Window.route`` breaks them with reason
        "non-resident").

        A launch that checks no coverage must not come after the block
        either: no fused window opens from the close below to the reset
        (``Window.held``).  One opened in between, by another host thread
        whose window this close ended, left its records to this read-back
        and launched behind it; its next deferral saw the reset, and the
        per-call compute that followed uploaded the host's copy over those
        launches (2 of 13 increments in the deterministic form,
        ``tests/test_fused.py``; 48 of 400 seen once in the threaded one).

        Lock order is safe: no other path holds two worker locks, and
        this thread takes the scheduler lock only nested inside (matching
        a phase's order)."""
        self.window.close(hold=True)
        TRACER.instant("resync", tag="part:locks")
        with ExitStack() as stack:
            stack.callback(self.window.release)
            for w in self.workers:
                stack.enter_context(w.lock)
            TRACER.instant("resync", tag="part:issue")
            pending, flush_iters = self.window.take_deferred()
            entries = start(pending, lock_each=False)
            TRACER.instant("resync", tag="part:join")
            self.finish_deferred(entries, flush_iters)
            TRACER.instant("resync", tag="part:reset")
            for w in self.workers:
                w.reset_coverage()

    def barrier(self, fence_split: bool) -> None:
        """``Cores.barrier``: close the fused window, fence every chip
        concurrently, feed the balancer each chip's retire time, arm the
        window's compute ids to rebalance, run the drain controller, end
        the enqueue window."""
        self.window.close()
        self._m_barriers.inc()
        _mt0 = time.perf_counter()
        t_b = TRACER.t0("fence")
        t0, window_cids, window_cid_order, window_iters_map, balanced_cids \
            = self.window.state()
        workers = self.workers
        measure = (self.settings.enqueue_mode and t0 is not None
                   and len(workers) > 1)
        split_order = (
            window_cid_order
            if (fence_split and measure and len(window_cids) > 1)
            else []
        )
        try:
            TRACER.instant("fence", tag="part:wait")
            if len(workers) == 1:
                workers[0].fence()
                return
            done_at: dict[int, float] = {}
            comp_at: dict[int, list[tuple[int, float]]] = {}

            def fence_timed(w: Worker) -> None:
                if FAULTS.enabled:
                    # injected lane stall (utils/faultinject.py): the
                    # lane's fence-retire wall inflates exactly like a
                    # real degradation — the chaos plane's barrier point
                    _d = FAULTS.delay_s(
                        "lane-stall", lane=w.index, where="barrier")
                    if _d > 0.0:
                        time.sleep(_d)
                comps: list[tuple[int, float]] = []
                for cid in split_order:
                    rng = self.ranges.get(cid)
                    if rng is not None and rng[w.index] <= 0:
                        continue  # this chip never ran the id
                    if w.fence_cid(cid):
                        comps.append((cid, time.perf_counter()))
                w.fence()
                done_at[w.index] = time.perf_counter()
                comp_at[w.index] = comps

            errs: list[Exception] = []
            futs = [self.pool.submit(TRACER.bind(fence_timed, w.index), w)
                    for w in workers]
            for f in futs:
                try:
                    f.result()
                except Exception as e:
                    errs.append(e)
            TRACER.instant("fence", tag="part:feed")
            if errs:
                record_crash(
                    "cores.barrier", errs[0], lanes=self._lane_config())
                raise errs[0]
            if measure:
                # lane health: each chip's fence-retire wall for this
                # window — the ck_fence_seconds-family signal the
                # eviction loop keys on.  Normalized by the window's
                # total iteration count, same scale rule as the benches
                # below and the transfer signal: a workload that grows
                # its window 4x is not a 4x-slower lane, and an
                # un-normalized feed would flip EVERY lane degraded on a
                # pure cadence change
                window_iters = max(1, sum(window_iters_map.values()))
                quarantined = self.drain.drained_lanes() \
                    if self.drain.enabled else set()
                for w in workers:
                    if w.index in quarantined:
                        # a share-0 lane ran nothing: its near-zero
                        # fence wall is not evidence, and letting it
                        # into the rolling baseline would make every
                        # later probe wall ratio as "degraded" against
                        # a corrupted near-zero baseline — the
                        # probation↔quarantine oscillation the chaos
                        # suite reproduced
                        continue
                    self.health.observe(
                        w.index, "fence",
                        (done_at[w.index] - t0) / window_iters)
                FLIGHT.event("barrier", lanes={
                    w.index: round((done_at[w.index] - t0) * 1000.0, 3)
                    for w in workers
                }, iters=window_iters)
                for w in workers:
                    bench = (done_at[w.index] - t0) * 1000.0
                    splits = split_fence_benches(comp_at.get(w.index, ()), t0)
                    window_ms = {
                        cid: splits.get(cid, bench)
                        for cid in balanced_cids
                        # only chips that ran this id refresh its bench;
                        # split marginals when available, whole-window
                        # fence time otherwise (the documented default)
                        if self.ranges.get(
                            cid, [1] * len(workers)
                        )[w.index] > 0
                    }
                    # under the worker lock: a driver thread's end_bench
                    # holds it — an unlocked update here could be lost
                    # against (or lose) that write (ckcheck finding)
                    with w.lock:
                        w.benchmarks.update(
                            per_iteration_benches(window_ms, window_iters_map)
                        )
                self.window.arm(balanced_cids)
        finally:
            TRACER.instant("fence", tag="part:close")
            REGISTRY.histogram(
                "ck_barrier_seconds", "barrier wall time",
            ).observe(time.perf_counter() - _mt0)
            # periodic metric sample into the flight ring (throttled —
            # at most one per FLIGHT.sample_interval_s)
            FLIGHT.maybe_sample_metrics()
            # throttled decision-log jsonl spill (armed by
            # CK_DECISION_LOG; a no-op attribute check otherwise) — the
            # barrier is the coldest periodic point the runtime has
            DECISIONS.maybe_spill()
            # drain actuation: the barrier is the ONE place quarantine
            # state moves (drains happen at window boundaries, never
            # mid-window); a state change arms a rebalance so the next
            # call re-splits — and in enqueue mode takes the existing
            # flush+coverage-reset path for the moved ranges
            self._drain_evaluate()
            # always close the window — a fence failure must not leave a
            # stale t0/cid set to corrupt the NEXT window's benches
            self.window.closed()
            # the span covers the barrier's bookkeeping too: a chip that
            # has retired its work waits for all of it
            TRACER.record("fence", t_b, tag="barrier")

    def _drain_evaluate(self) -> None:
        """Run one DrainController transition (barrier tail).  Guarded:
        it runs inside the barrier's ``finally``, where an exception
        would mask the fence error the barrier exists to surface."""
        try:
            res = self.drain.evaluate()
        except Exception as e:  # noqa: BLE001 - must not mask fence errors
            FLIGHT.event("drain-apply", error=f"{type(e).__name__}: {e}"[:200])
            return
        if res and (res["drained"] or res["readmitted"] or res["probed"]):
            self.window.arm(self.ranges.keys())
