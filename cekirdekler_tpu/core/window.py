"""The enqueue window: what ``Cores.compute`` has dispatched since the last
sync point, and the fused-iteration dispatch that collapses a window's
repeated computes into ladder launches.

Enqueue mode (reference: ClNumberCruncher.cs:125-129, Cores.cs:836-949)
skips host synchronization and readbacks: a compute leaves deferred-readback
RECORDS here, ``flush()`` and a range move read them back (``core/sync.py``),
and ``barrier()`` closes the window and arms a rebalance of the compute ids
it saw (the reference pins enqueue-mode work to one device; here the ranges
hold still BETWEEN syncs and move AT them).

Fused-iteration dispatch (the enqueue dispatch-floor collapse): when a
window repeats the same compute id with unchanged ranges and HBM-resident
operands, its calls are DEFERRED (a counter increment) and dispatched in
batches as ONE dynamic-iteration-count ladder executable per device
(``Worker.launch_fused`` / ``KernelProgram.fused_launcher``), through a
depth-limited per-device driver queue so device B's ladder dispatch overlaps
device A's execution.  :meth:`Window.route` is the ONE place that says how a
compute goes: deferred into the open window, deferred as the first iteration
of a window opened on the ladder, or per call (and why).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Sequence

from ..arrays.clarray import ClArray
from ..kernel.registry import KernelProgram, lowering_meta
from ..metrics.registry import REGISTRY
from ..obs.decisions import DECISIONS
from ..obs.drain import DrainController
from ..obs.flight import FLIGHT, record_crash
from ..trace.spans import TRACER
from .compilecache import CACHE as COMPILE_CACHE
from .compilecache import record_engaged
from .worker import Worker

__all__ = ["Window", "DEFERRED", "job_signature", "sig_equal", "holds",
           "reads_back", "write_all_owners"]

#: :meth:`Window.route`'s answer for a compute that was counted into a window
DEFERRED = "deferred"


def job_signature(
    kernel_names, params, compute_id, global_range, local_range,
    global_offset, value_args,
) -> tuple:
    """Identity of one repeatable enqueue call — THE coalescing key.
    One function on purpose: the fused-window machinery and the serving
    tier's request grouping (``serve.frontend.ServeJob.signature``) must
    build the identical tuple, else batches silently stop matching open
    windows and every dispatch rides the per-call fallback.  Params enter
    by OBJECT identity: the workers' buffer caches key on ``id(arr)``, so
    a different array object is a different dispatch even at equal
    shapes."""
    if isinstance(value_args, dict):
        vals: Any = tuple(
            (k, tuple(v)) for k, v in sorted(value_args.items())
        )
    else:
        vals = tuple(value_args)
    return (
        compute_id, tuple(kernel_names), tuple(id(p) for p in params),
        global_range, local_range, global_offset, vals,
    )


def sig_equal(a: tuple | None, b: tuple | None) -> bool:
    """Signature equality that treats ANY comparison failure as a
    mismatch: array-valued value args make tuple ``==`` raise (ambiguous
    elementwise truth) — such a call must take the signature-change path,
    never crash mid-window."""
    if a is None or b is None:
        return False
    try:
        return bool(a == b)
    except Exception:  # noqa: BLE001 - mismatch by definition
        return False


def holds(w: Worker, p: ClArray, offset: int, size: int) -> bool:
    """THE enqueue-mode residency test: whether lane ``w`` holds what a
    launch over work items ``[offset, offset + size)`` reads of ``p`` — the
    items' own elements of a ``partial_read`` array, else the whole array.
    Range-aware, so a rebalance between syncs forces a fetch of the moved
    region (``Worker.upload_covers``)."""
    fl = p.flags
    if not fl.partial_read:
        return w.upload_covers(p, 0, p.size)
    epw = fl.elements_per_work_item
    return w.upload_covers(p, offset * epw, size * epw)


def write_all_owners(params: Sequence[ClArray], active: Sequence[int]) -> dict:
    """``{position: lane}`` — "device i writes array (i mod numDevices)"
    (Worker.cs:871-885), but only among the lanes that actually run, else
    a starved owner would silently skip the readback."""
    return {idx: active[idx % len(active)] for idx, p in enumerate(params)
            if p.flags.write_all and active}


def reads_back(flags, lane: int, owner: int | None) -> bool:
    """Whether ``lane`` reads an array of these flags back after a launch:
    every lane its own range of what the kernels write, a ``write_all``
    array its owner alone (N racing whole-array readbacks are wrong and
    wasteful)."""
    return bool(flags.write and not flags.read_only
                and (not flags.write_all or lane == owner))


@dataclass
class _FusedRun:
    """State of one ACTIVE fused-iteration window: the signature every
    deferral is matched against, plus everything needed to dispatch the
    accumulated iterations as one ladder per device at a flush point."""

    sig: tuple
    compute_id: int
    kernel_names: tuple
    params: tuple
    value_args: Any
    local_range: int
    global_range: int
    step: int
    # per active worker: (worker, global offset, range size)
    rows: list = field(default_factory=list)
    # coverage-epoch snapshot at engage: (worker, epoch) — ONE int compare
    # per worker per deferral detects any mid-window coverage reset
    epochs: list = field(default_factory=list)
    # how many pending iterations the NEXT flush of Window.defer waits for
    ramp: int = 1
    # iterations this window has dispatched: a window that deferred at
    # least one has built (or found) its ladder executable on every lane
    dispatched: int = 0
    # what the window left when it closed: per row, weak references to
    # the lane's buffers of ``params`` after its last dispatch (the next
    # window of this signature starts on the ladder only over these)
    left: list = field(default_factory=list)


class Window:
    """The enqueue-window ledger and the fused-window state of one
    scheduler.  ``ranges`` / ``refs`` are the scheduler's range tables and
    ``owners`` the exchange's map of who holds which elements, all shared
    by reference and only read here; ``record_perf`` and ``lane_config``
    are the scheduler's (a dispatched window's perf row, the postmortem's
    lane block).

    WHICH LOCK GUARDS WHICH FIELD (the one table):

    - ``lock``, the SCHEDULER lock: every WRITE of ``t0``, ``cids``,
      ``cid_order``, ``iters``, ``flush_iters``, ``enqueued``, ``seq``,
      ``rebalance``, ``exchanging``, ``sig``, ``run``, ``candidate``,
      ``last``, ``pending``, ``held``, the counts and dicts of ``stats``,
      ``run.ramp`` / ``run.dispatched``; also the exchange's ``owners``
      and the scheduler's verdict dedupe.
    - read WITHOUT it: ``sig`` / ``run`` (:meth:`route`'s fast path: one
      attribute read a call, revalidated under the lock in :meth:`defer` —
      the stale-read window is the design, the locked revalidation is the
      correctness); ``t0`` (a window id is an observation aid);
      ``rebalance`` (one-shot arm: a stale read delays the rebalance by a
      call); ``candidate`` / ``last`` / ``held`` (:meth:`start`,
      revalidated under the lock before it opens); ``stats`` (reporting;
      the counts only grow).
    - ``_mu`` guards no field: it serialises [grab ``pending`` + submit to
      the lanes' drivers] so that a close/drain cannot slip between a
      concurrent flush's grab and its submits (downloads would then
      precede the in-flight ladder and the host would miss iterations).
    - ``batch_preflighted``: no lock, single writer by the enqueue
      single-driver contract.
    - ORDER: ``_mu`` -> ``lock``; ``Worker.lock`` -> ``lock`` (a phase
      leaves its records under its lane's lock); ``_mu`` ->
      ``_DriverQueue._cond``.  Never the reverse of any."""

    def __init__(self, settings, program: KernelProgram,
                 workers: list[Worker], ranges: dict, refs: dict,
                 drain: DrainController, owners: dict,
                 record_perf: Callable, lane_config: Callable):
        self.settings = settings
        self.program = program
        self.workers = workers
        self.ranges = ranges
        self.refs = refs
        self.drain = drain
        self.owners = owners
        self._record_perf = record_perf
        self._lane_config = lane_config
        self.lock = threading.Lock()
        self._mu = threading.Lock()
        # deferred-readback records: (seq, worker, array, offset, size,
        # write_all, compute_id) — cid rides along so the flush drain
        # can attribute each lane's D2H wall back to the balancer
        self.enqueued: list[tuple] = []
        # monotone tag on the records: the flush orders host writes by it
        self.seq = 0
        # per-cid iteration count since the last FLUSH (not the last
        # window — ``iters`` resets per barrier): the drain's divisor, so
        # the transfer feed lands in the same per-ITERATION milliseconds
        # the enqueue benches use (a per-flush total vs a per-iteration
        # bench would over-floor every lane ~window-size-x)
        self.flush_iters: dict[int, int] = {}
        # compute ids dispatched since the last barrier, in LAST-dispatch
        # order (the fence split probes completions ascending, and a
        # cid's last launch is what its probe waits on), when the window
        # opened, and its iteration counts per compute id (the barrier
        # feeds the balancer per-ITERATION benches)
        self.cids: set[int] = set()
        self.cid_order: list[int] = []
        self.iters: dict[int, int] = {}
        # ckcheck: ok racy read — a window id is an observation aid
        self.t0: float | None = None
        # the ids whose benches a barrier refreshed: those MAY rebalance
        # on their next call (the sync-granularity analogue of feeding
        # event benches into loadBalance, HelperFunctions.cs:190-280)
        # ckcheck: ok one-shot arm: a stale read only delays the
        # rebalance by one call; arm/disarm writes hold the lock
        self.rebalance: set[int] = set()
        # compute ids whose computes exchanged in the open window: said
        # once why they do not fuse, and left out of the barrier's feed to
        # the balancer (their lanes retire in lock-step: see Sync.barrier)
        self.exchanging: set[int] = set()
        # ckcheck: ok racy fast-path read, revalidated in defer
        self.sig: tuple | None = None
        # ckcheck: ok racy fast-path read, revalidated in defer
        self.run: _FusedRun | None = None
        # the last per-call enqueue signature (try_engage: a window
        # engages only on a CONSECUTIVE repeat)
        # ckcheck: ok racy read in start — its open revalidates under the lock
        self.candidate: tuple | None = None
        # the last window that closed after deferring at least one
        # iteration: what start() opens the next window from
        # ckcheck: ok racy read in start — a closed run is never written
        # again, and the open revalidates under the lock
        self.last: _FusedRun | None = None
        self.pending = 0
        # how many host threads are between closing the fused window and
        # the end of their read-back of the deferred results (close(hold=
        # True) .. release()): no window opens meanwhile.  One opened there
        # would leave its records to THAT read-back and launch after it
        # (Sync.flush_and_reset_coverage says what is lost then)
        self.held = 0
        # True while Cores.compute_fused_batch runs a per-call iteration it
        # already lane-preflighted: stream-driver submits inside it skip
        # their own fault fire (a mid-phase fire would be a dirty
        # cross-lane failure containment cannot repair)
        self.batch_preflighted = False
        # windows dispatched, iterations fused, every disengage with its
        # named reason and how each enqueue window's first compute went
        # ("ladder" or why per call): a regression to the per-iteration
        # path must be attributable, never silent.  The per-cruncher API
        # (``fused_stats``); the ck_fused_* series carry the same counts.
        # ckcheck: ok reporting-only reads; monotone counters, snapshot semantics
        self.stats: dict[str, Any] = {
            "windows": 0, "fused_iters": 0, "deferred_iters": 0,
            "disengaged": {}, "window_starts": {},
        }
        # cached handles for the warm path (one dispatch per batch; the
        # deferral itself counts into ``stats`` alone); the per-reason
        # counters stay get-or-create: they are cold
        self._m_windows = REGISTRY.counter(
            "ck_fused_windows_total", "fused ladder dispatch batches")
        self._m_iters = REGISTRY.counter(
            "ck_fused_iters_total",
            "iterations dispatched via fused ladders")

    # -- the ledger ----------------------------------------------------------
    def _note_call(self, compute_id: int, t_start: float) -> None:
        """Window bookkeeping shared by the per-call and deferred paths
        (the cid order feeds the fence split, the iteration counts the
        balancer's per-iteration benches).  Caller holds the lock."""
        if self.t0 is None:
            self.t0 = t_start
        if compute_id in self.cids:
            self.cid_order.remove(compute_id)
        self.cid_order.append(compute_id)
        self.cids.add(compute_id)
        self.iters[compute_id] = self.iters.get(compute_id, 0) + 1
        self.flush_iters[compute_id] = self.flush_iters.get(compute_id, 0) + 1

    def note_call(self, compute_id: int, t_start: float) -> None:
        """A per-call compute of the window (the order list's
        remove+append is not atomic like the set add is)."""
        with self.lock:
            self._note_call(compute_id, t_start)

    def _defer_readback(self, w: Worker, p: ClArray, offset: int,
                        size: int, compute_id: int) -> None:
        self.seq += 1
        self.enqueued.append(
            (self.seq, w, p, offset, size, p.flags.write_all, compute_id))

    def defer_readback(self, w: Worker, p: ClArray, offset: int,
                       size: int, compute_id: int) -> None:
        """One deferred-readback record: ``flush()`` and a range move
        read back the newest a lane and array."""
        with self.lock:
            self._defer_readback(w, p, offset, size, compute_id)

    def take_deferred(self) -> tuple[list, dict]:
        """The records and per-cid iteration counts since the last flush."""
        with self.lock:
            pending, self.enqueued = self.enqueued, []
            iters, self.flush_iters = self.flush_iters, {}
        return pending, iters

    def arm(self, cids) -> None:
        """These compute ids may rebalance on their next call (``|=`` is
        a read-modify-write: a concurrent :meth:`disarm` must not be
        interleaved into it, nor un-arm what a barrier just armed)."""
        with self.lock:
            self.rebalance |= set(cids)

    def disarm(self, compute_id: int) -> None:
        with self.lock:
            self.rebalance.discard(compute_id)

    def note_exchanging(self, compute_id: int) -> bool:
        """A compute of this id exchanged in the open window; whether
        that was already said."""
        with self.lock:
            said = compute_id in self.exchanging
            self.exchanging.add(compute_id)
        return said

    def state(self) -> tuple:
        """What a barrier feeds the balancer from, as ONE consistent
        snapshot: another host thread's compute() mutates the window
        mid-barrier, and point reads could pair a cid with an iteration
        count not yet bumped (a mismatched divisor).  Returns ``(t0, cids,
        cid order, iterations, the ids to balance)``: an id whose computes
        exchanged keeps its benches and arms no rebalance."""
        with self.lock:
            return (self.t0, set(self.cids), list(self.cid_order),
                    dict(self.iters), self.cids - self.exchanging)

    def closed(self) -> None:
        """The barrier ends the enqueue window (an unlocked clear could
        fall between :meth:`_note_call`'s check and its remove)."""
        with self.lock:
            self.cids.clear()
            self.cid_order.clear()
            self.iters.clear()
            self.t0 = None
            self.exchanging.clear()

    def snapshot(self) -> dict:
        """``/statusz``'s view of the window, one consistent copy."""
        with self.lock:
            return {
                "enqueue_window": {
                    "enqueue_mode": self.settings.enqueue_mode,
                    "active_cids": sorted(self.cids),
                    "cid_order": list(self.cid_order),
                    "iters": dict(self.iters),
                    "window_age_s": (
                        round(time.perf_counter() - self.t0, 6)
                        if self.t0 is not None else None),
                    "fused_window_open": self.sig is not None,
                    "fused_pending": self.pending,
                },
                "shares": {str(cid): list(r)
                           for cid, r in self.ranges.items()},
                "fused": {
                    **{k: self.stats[k] for k in (
                        "windows", "fused_iters", "deferred_iters")},
                    "disengaged": dict(self.stats["disengaged"]),
                    "window_starts": dict(self.stats["window_starts"]),
                },
            }

    # -- how a compute goes --------------------------------------------------
    def route(self, sig: tuple | None, kernel_names, compute_id: int,
              global_offset: int, pipeline: bool, t_start: float, span,
              opens: bool):
        """How this compute goes: :data:`DEFERRED` (counted into a fused
        window: the call is done), else per call, and the answer is WHY a
        compute that found no window open did not start one on the ladder
        (``None`` outside enqueue mode and where a window was open and
        broke: every break-out names its reason in ``stats`` and a
        "fused" trace instant, so a regression to per-iteration dispatch
        is attributable).  ``sig``: the call's :func:`job_signature` (an
        enqueue-mode compute that is not pipelined has one); ``span``: the
        caller's open "enqueue" span; ``opens``: this call is the first
        of its enqueue window."""
        s = self.settings
        # ckcheck: ok racy fast-path read, revalidated in defer
        if self.sig is None:
            if not s.enqueue_mode:
                return None
            # no fused window is open.  One that repeats the last window,
            # over the buffers that window left, starts on the ladder:
            # this call is its first deferred iteration, and nothing of
            # the per-call path runs (start says why not)
            how = "mode" if pipeline else self.start(
                sig, compute_id, global_offset)
            if how is None:
                if self.defer(t_start, kernel_names, span,
                              "ladder" if opens else ""):
                    if opens:
                        self.note_start("ladder")
                    return DEFERRED
                how = "closed"  # by another thread, before the deferral
            return how
        run = self.run
        if pipeline:
            self.end("pipeline")
        elif not s.enqueue_mode:
            # leaving enqueue mode without flush() (callers normally go
            # through the cruncher setter, which flushes)
            self.end("enqueue-off")
        elif not sig_equal(sig, self.sig):
            self.end("signature-change")
        elif self._modes_off():
            # clear the candidate so this call's tail records ONE event
            # ("mode-change"), not a second engage-refusal under another
            # name for the same call.  Under the lock: concurrent host
            # threads' engage tails write it (an unlocked clear could
            # resurrect a candidate another thread just replaced)
            with self.lock:
                self.candidate = None
            self.end("mode-change")
        elif compute_id in self.rebalance:
            # a barrier armed a rebalance: ranges may move — the
            # window's pinned per-device rows are no longer valid
            self.end("range-change")
        elif run is not None and any(
                w.coverage_epoch != ep for w, ep in run.epochs):
            # a sync-point rebalance (possibly another thread's) reset
            # upload coverage mid-window: operands are no longer
            # guaranteed HBM-resident for these rows
            self.end("non-resident")
        elif self.defer(t_start, kernel_names, span):
            return DEFERRED
        return None

    def _modes_off(self) -> bool:
        """A runtime mode toggle that no fused window may run under.  They
        are cruncher state, not part of a call's signature: every deferral
        and every window start re-checks them, else flipping one
        mid-window would silently defer a call whose semantics changed
        (``repeat_count=3`` deferring as ONE iteration)."""
        s = self.settings
        return bool(
            not s.fused_dispatch
            or s.no_compute_mode
            or s.repeat_count > 1
            or s.repeat_sync_kernel
            or s.dispatch_gate is not None
        )

    def rows_of(self, ranges, refs, global_offset: int) -> list:
        """A fused window's rows, ``(worker, global offset, range size)``
        for every lane with a share, from a compute id's range table."""
        return [(w, global_offset + refs[i], ranges[i])
                for i, w in enumerate(self.workers) if ranges[i] > 0]

    @staticmethod
    def _rows_covered(rows, params) -> bool:
        """Whether every array the kernels read is resident on every
        row's lane over the range a launch there reads: the deferral
        contract is a pure launch."""
        return all(holds(w, p, off, size) for w, off, size in rows
                   for p in params
                   if p.flags.read and not p.flags.write_only)

    def try_engage(
        self, sig: tuple, kernel_names, params, compute_id, global_range,
        local_range, global_offset, value_args, ranges, refs, step,
    ) -> None:
        """After a per-call compute of an enqueue window: open a fused
        window for this call's signature, or record WHY not
        (``stats["disengaged"]`` + a "fused" trace instant) — every
        refusal reason is observable so a silent fall-back to
        per-iteration dispatch cannot masquerade as device slowness.

        Engagement requires a CONSECUTIVE repeat of the signature: the
        first sighting only seeds the candidate, so a window that never
        repeats (mixed cids alternating every call) costs one tuple
        compare per call — no engage walk, no break/drain cycle, and no
        misleading disengage stats for calls that were never going to
        fuse."""
        # swap under the lock: with concurrent host threads an unlocked
        # read-modify-write could interleave with another thread's swap
        # and engage a window off a candidate that thread already replaced
        with self.lock:
            candidate, self.candidate = self.candidate, sig
        if not sig_equal(sig, candidate):
            return
        s = self.settings
        reason = None
        if s.no_compute_mode:
            reason = "no-compute"
        elif s.repeat_count > 1 or s.repeat_sync_kernel:
            # each call already fuses its repeats on device
            # (sequence_launcher); cross-call fusion would change the
            # sync-kernel interleaving contract
            reason = "repeat-mode"
        elif s.dispatch_gate is not None:
            reason = "dispatch-gate"
        if reason is None:
            try:
                hash(sig)
            except TypeError:
                reason = "unhashable-values"
        rows: list = []
        if reason is None:
            rows = self.rows_of(ranges, refs, global_offset)
            if not self._rows_covered(rows, params):
                # this call needed a partial upload the window would have
                # to repeat — the deferral contract (pure launch) fails
                reason = "partial-upload"
        if reason is not None:
            self.note_disengage(reason, compute_id)
            return
        run = _FusedRun(
            sig=sig, compute_id=compute_id,
            kernel_names=tuple(kernel_names), params=tuple(params),
            value_args=value_args, local_range=local_range,
            global_range=global_range, step=step, rows=rows,
            # ckcheck: ok monotone epoch int — one GIL-atomic read
            epochs=[(w, w.coverage_epoch) for w, _off, _size in rows],
        )
        with self.lock:
            held = self.held
            if not held:
                self.sig = sig
                self.run = run
        if held:
            self.note_disengage("resync", compute_id)
        else:
            self._engaged(run)

    def _engaged(self, run: _FusedRun) -> None:
        """What every opened fused window records, however it opened."""
        compute_id, rows = run.compute_id, run.rows
        FLIGHT.event("fused-engage", cid=compute_id, rows=len(rows))
        # persistent-cache seam (core/compilecache.py): an engaged
        # window's spec is what a joining process would need to warm
        # (engagement is cold: once per window open, never the defer path)
        if COMPILE_CACHE.enabled:
            record_engaged(self.program, self.workers, run)
        if DECISIONS.enabled:
            # provenance (not replayable: the engage check reads LIVE
            # device residency) — what signature fused, on which lanes
            DECISIONS.record("fused-engage", {
                "cid": compute_id,
                "kernels": list(run.kernel_names),
                "global_range": run.global_range,
                "local_range": run.local_range,
                "lanes": [w.index for w, _off, _size in rows],
            }, {"engaged": True, "rows": len(rows)})

    def _ladder_of(self, run: _FusedRun, w: Worker, off: int, size: int):
        """A PEEK at the row's fused executable: ``None`` where it was
        never built (a window of one compute never compiles a ladder)."""
        return self.program.fused_launcher(
            run.kernel_names, run.step, run.global_range, run.local_range,
            run.global_range, run.value_args, platform=w.device.platform,
            donate=w.fused_donate, build=False,
            in_range=0 <= off and off + size <= run.global_range)

    def start(self, sig: tuple, compute_id: int,
              global_offset: int) -> str | None:
        """A compute in enqueue mode found no fused window open: open one
        ON THE LADDER if this call repeats the last window, so that it is
        deferred as the window's first iteration and the per-call path
        (verify, range table, the pool hop, a per-call launch that hands
        its values over at run time) never runs.  Returns ``None`` when
        the window is open, else the named reason the call goes per call
        (``stats["window_starts"]``), the first that holds of:

        - ``mode``: a runtime toggle no fused window runs under;
        - ``resync``: another host thread is reading the deferred results
          back (``held``);
        - ``first-sighting`` / ``values-changed``: the signature is not
          the last per-call or fused one (the candidate, which survives a
          barrier); only its values differ, or more;
        - ``range-change``: a barrier (or a drain transition) armed a
          rebalance of this compute id, a lane is drained or on probation,
          or the range table no longer reads what the last window ran;
        - ``halo``: some compute of this scheduler reads across lanes
          (who holds which elements is tracked per compute: no deferral);
        - ``never-fused``: the last window of this signature deferred
          nothing, so no ladder executable of this key was ever built;
        - ``non-resident``: upload coverage was reset since, or a lane's
          buffers are no longer the ones that window left (an upload, a
          launch of another compute);
        - ``partial-upload``: an array the kernels read is not covered;
        - ``closed``: another host thread opened or closed a window between
          this call's checks and its deferral.

        What the per-call first compute leaves behind for later is left
        here too: the deferred-readback records (``flush()`` and a range
        move read them); :meth:`defer` does the window bookkeeping."""
        if self._modes_off():
            return "mode"
        # ckcheck: ok racy read — the open below revalidates under the lock
        if self.held:
            return "resync"
        candidate = self.candidate
        if not sig_equal(sig, candidate):
            same_but_values = (candidate is not None
                               and candidate[:-1] == sig[:-1])
            return "values-changed" if same_but_values else "first-sighting"
        if compute_id in self.rebalance or (
                self.drain.enabled and (self.drain.drained_lanes()
                                        or self.drain.probe_lanes())):
            return "range-change"
        # ckcheck: ok racy emptiness peek — a compute's own arrays enter
        # the map on its own thread
        if self.owners:
            return "halo"
        last = self.last
        if last is None or not sig_equal(last.sig, sig):
            return "never-fused"
        ranges = self.ranges.get(compute_id)
        refs = self.refs.get(compute_id)
        if (ranges is None or refs is None
                or self.rows_of(ranges, refs, global_offset) != last.rows):
            return "range-change"
        if any(self._ladder_of(last, *row) is None for row in last.rows):
            return "never-fused"
        for (w, epoch), left in zip(last.epochs, last.left):
            # ckcheck: ok monotone epoch int — one GIL-atomic read
            if w.coverage_epoch != epoch or not w.still_holds(
                    last.params, left):
                return "non-resident"
        if not self._rows_covered(last.rows, last.params):
            return "partial-upload"
        run = replace(last, ramp=1, dispatched=0, left=[])
        owner = write_all_owners(
            run.params, [w.index for w, _off, _size in run.rows])
        with self.lock:
            if self.sig is not None:
                return "closed"  # another thread opened a window meanwhile
            if self.held:
                return "resync"
            if any(w.coverage_epoch != epoch for w, epoch in run.epochs):
                return "non-resident"
            for idx, p in enumerate(run.params):
                for w, off, size in run.rows:
                    if reads_back(p.flags, w.index, owner.get(idx)):
                        self._defer_readback(w, p, off, size, compute_id)
            self.sig = sig
            self.run = run
        self._engaged(run)
        return None

    def note_start(self, how: str) -> None:
        """How an enqueue window's first compute went: ``ladder`` or the
        reason it took the per-call path; the dict and the registry carry
        the same counts."""
        with self.lock:
            d = self.stats["window_starts"]
            d[how] = d.get(how, 0) + 1
        REGISTRY.counter(
            "ck_fused_window_start_total",
            "enqueue windows by how their first compute went", how=how,
        ).inc()

    def defer(self, t_start: float, kernel_names, span=0.0,
              start: str = "") -> bool:
        """Count this call into the active fused window.  Returns False
        when the window was concurrently closed (caller falls through to
        the per-call path).  ``span`` is the caller's open "enqueue" span
        (falsy while the tracer is inactive); ``start`` rides it where
        this call opened its enqueue window on the ladder.

        The eager sub-batch RAMPS: the pending iterations are dispatched
        once they number ``run.ramp``, which starts at 1 when a window
        opens and doubles with every such dispatch up to ``fused_batch``
        (1, 2, 4, 8, 16, 16, ...): the device starts on the window's first
        deferred iteration, and each dispatch goes out while the one
        before it runs.  A count, not a probe; and the budget of a
        deferral is "a counter increment": no perf row is built here (one
        lands per dispatched window, in :meth:`_dispatch`)."""
        with self.lock:
            run = self.run
            if run is None or self.sig is None:
                return False
            cid = run.compute_id
            self._note_call(cid, t_start)
            self.pending += 1
            cap = max(1, int(self.settings.fused_batch))
            due = self.pending >= min(run.ramp, cap)
            if due:
                run.ramp = min(2 * run.ramp, cap)
            self.stats["deferred_iters"] += 1
        if due:
            self.flush()
        if TRACER.active():
            # guard the WHOLE call: the tag concatenation allocates per
            # deferral even when the tracer is off, and the deferral is
            # the path whose cost budget is "a counter increment"
            # (ckcheck hotpath finding, PR 7)
            TRACER.record(
                "enqueue", span, cid=cid,
                tag="+".join(kernel_names) + " fused-defer",
                **({"start": start} if start else {}),
            )
        return True

    def _poison(self) -> None:
        """A failed dispatch: no caller that catches the error may keep
        deferring into this window (the next call goes per call)."""
        with self.lock:
            self.sig = None
            self.run = None
            self.candidate = None

    def _dispatch(self, run: _FusedRun, iters: int) -> None:
        """Submit one K-iteration ladder dispatch per active device to the
        per-device driver queues (host-side dispatch of device B's ladder
        overlaps device A's execution; FIFO per device)."""
        _tt = TRACER.t0("fused")
        _t_pass = time.perf_counter()
        try:
            # PREFLIGHT every lane before queuing ANY lane's closure:
            # pending driver errors and the armed driver-submit fault
            # point raise here, where no device has been handed this
            # batch yet — a refusal is then CLEAN (no diverged iteration
            # counts) and the serving tier's containment can re-dispatch
            # the residue bit-exactly.  One counted fault hit per lane
            # either way (submit skips its own fire when preflighted).
            # The worker preflight stamps _ck_clean_window per raise
            # source: True for the injected fault (fired before any
            # closure queued), False for a popped pending error (an
            # EARLIER closure's work never applied — re-dispatch could
            # silently corrupt)
            for w, _off, _size in run.rows:
                w.dispatch_preflight()
            for w, off, size in run.rows:
                def dispatch(w=w, off=off, size=size, run=run, iters=iters):
                    with w.lock:
                        w.start_bench(run.compute_id)
                        try:
                            w.launch_fused(
                                self.program, run.kernel_names, run.params,
                                run.value_args, off, size, run.local_range,
                                run.global_range, run.step, iters,
                                compute_id=run.compute_id,
                            )
                        finally:
                            w.end_bench(run.compute_id)

                # a submit failure here (a driver re-raising an error a
                # closure hit since the preflight) after some rows were
                # queued leaves devices with DIVERGED iteration counts
                # for this batch
                w.dispatch_async(dispatch,
                                 depth=self.settings.fused_queue_depth,
                                 preflighted=True)
        except Exception:
            self._poison()
            raise
        with self.lock:
            run.dispatched += iters
            self.stats["windows"] += 1
            self.stats["fused_iters"] += iters
        self._m_windows.inc()
        self._m_iters.inc(iters)
        # one perf row per dispatched window (total_ms = this dispatch
        # pass) — the per-window row the per-deferral fast path does not
        # pay for
        self._record_perf(run.compute_id, _t_pass,
                          self.ranges.get(run.compute_id, []))
        FLIGHT.event("fused-window", cid=run.compute_id, iters=iters)
        if _tt:
            # the lowering of the rungs in the lanes' fused executables: a
            # peek, so a process's FIRST window, whose executables the
            # closures above are still to trace, has none to name (its
            # lanes' ``launch`` spans do)
            fns = [self._ladder_of(run, *row) for row in run.rows]
            infos = [fn.info for fn in fns if fn is not None and fn.info.rungs]
            TRACER.record(
                "fused", _tt, cid=run.compute_id, tag=f"x{iters}",
                **(lowering_meta(infos) if infos else {}))

    # ckcheck: cold window boundary — runs once a sub-batch of the ramp
    def flush(self, close: bool = False, hold: bool = False):
        """Dispatch the accumulated deferred iterations; ``close`` also
        stops deferrals (and ``hold``s windows shut).  Under _mu so that a
        concurrent close cannot drain the drivers between this grab of
        ``pending`` and its submits.  Returns the window's run."""
        with self._mu:
            with self.lock:
                run, k = self.run, self.pending
                self.pending = 0
                if close:
                    self.sig = self.run = None
                    self.held += hold
                    if run is not None:
                        self.last = None
            if run is not None and k > 0:
                self._dispatch(run, k)
        return run

    def close(self, hold: bool = False) -> None:
        """End the fused window at a sync point: stop deferrals, dispatch
        the residue, and drain the per-device drivers (host-side dispatch
        complete — device completion is the caller's fence).  A window that
        deferred anything is kept as ``last``: the next window re-engages
        through its first per-call iteration, or, where it repeats this
        one, starts on the ladder (:meth:`start`).  ``hold``: no window
        opens until :meth:`release` (the caller reads the deferred results
        back: ``held``)."""
        run = self.flush(close=True, hold=hold)
        _td = TRACER.t0("drain")
        try:
            self._drain()
        finally:
            TRACER.record("drain", _td)
        if run is not None and run.dispatched:
            # the drivers have drained: the lanes hold what the window's
            # last dispatch left
            run.left = [w.buffers_left(run.params) for w, _o, _s in run.rows]
            with self.lock:
                self.last = run

    def release(self) -> None:
        """The end of a read-back that :meth:`close` held windows for."""
        with self.lock:
            self.held -= 1

    def _drain(self) -> None:
        errs: list[Exception] = []
        for w in self.workers:
            try:
                w.drain_dispatch()
            except Exception as e:  # noqa: BLE001 - surfaced below
                errs.append(e)
        if errs:
            # a driver-queue failure surfaces HERE (the window's sync
            # point) — the postmortem's canonical trigger: the dump
            # carries the engage/disengage events and the driver-error
            # span that preceded this raise
            record_crash(
                "cores.fused_drain", errs[0], lanes=self._lane_config())
            raise errs[0]

    def note_disengage(self, reason: str, cid: int | None) -> None:
        """The one disengage-accounting path: stats dict bump, flight
        event, decision row and "fused" trace instant."""
        with self.lock:
            d = self.stats["disengaged"]
            d[reason] = d.get(reason, 0) + 1
        FLIGHT.event("fused-disengage", reason=reason, cid=cid)
        if DECISIONS.enabled:
            DECISIONS.record(
                "fused-disengage", {"cid": cid}, {"reason": reason})
        TRACER.instant("fused", cid=cid, tag=f"disengage:{reason}")

    def end(self, reason: str) -> None:
        """:meth:`close` plus the disengage bookkeeping."""
        with self.lock:
            run = self.run
        cid = run.compute_id if run is not None else None
        self.close()
        self.note_disengage(reason, cid)

    # -- externally-assembled batches (the serving tier's entry) -------------
    def defer_many(self, sig: tuple, k: int, t_start: float) -> bool:
        """Count ``k`` iterations into the open fused window matching
        ``sig`` in ONE step, then flush, so the whole batch lands as ONE
        ladder dispatch per device.  Returns False when no healthy
        matching window is open; the guard re-checks exactly what the
        per-call deferral re-checks: runtime mode toggles, an armed
        rebalance, and the coverage epoch (a mid-batch reset means
        operands are no longer guaranteed HBM-resident)."""
        with self.lock:
            run = self.run
            if (
                run is None
                or not sig_equal(self.sig, sig)
                or self._modes_off()
                or run.compute_id in self.rebalance
                or any(w.coverage_epoch != ep for w, ep in run.epochs)
            ):
                return False
            cid = run.compute_id
            # ONE order-list touch + bulk iteration-count bumps: k
            # repeated _note_call calls would pay k redundant
            # remove/append cycles on the cid order list while holding
            # the lock against every concurrent deferral
            self._note_call(cid, t_start)
            if k > 1:
                self.iters[cid] += k - 1
                self.flush_iters[cid] += k - 1
            self.pending += k
            self.stats["deferred_iters"] += k
        self.flush()
        return True
