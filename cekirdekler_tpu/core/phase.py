"""One lane's phase of a per-call compute: what crosses to the chip, the
launches, what crosses back (reference: Cores.cs:746-835 / 1197-1980).

:func:`classify` is the ONE statement of which arrays of a phase go up whole,
go up over the lane's range, are resident already, and which come back over
the range, after the last launch, deferred to the window's end, or from the
``write_all`` owner alone.  Four engines consume its answer and keep only
their own ORDER of issue (:meth:`Phases.run` chooses):

- **monolithic**: upload, launch, download, each in one piece;
- **STREAM** (``streamed_transfers``, the default where anything can
  overlap): the lane's range cut into ladder-aligned chunks
  (:meth:`Phases._streamed`); ``stream_chunks`` 0 = autotune, n = pin;
- **DRIVER** / **EVENT** (``compute(pipeline=True)``): the range cut into
  ``pipeline_blobs`` sub-ranges, blob k+1's H2D issued while blob k
  computes (:meth:`Phases._driver`, :meth:`Phases._event`).
"""

from __future__ import annotations

import time
from typing import NamedTuple, Sequence

from ..arrays.clarray import ClArray
from ..kernel.registry import KernelProgram
from ..obs.flight import FLIGHT
from ..obs.health import HealthMonitor
from ..trace.spans import TRACER
from .stream import TransferTuner, chunk_plan
from .window import Window, holds, reads_back
from .worker import Worker

__all__ = ["Phases", "Job", "Crossing", "classify", "tuner_kernel_key",
           "PIPELINE_EVENT", "PIPELINE_DRIVER"]

PIPELINE_EVENT = 1   # reference: Cores.cs:416-423
PIPELINE_DRIVER = 2

# how an array crosses BEFORE the first launch (None: resident already) ...
WHOLE, PART, ENSURE = "whole", "part", "ensure"
# ... and AFTER (None: nothing from this lane; PART as above)
LATE, OWNER, DEFER = "late", "owner", "defer"


class Job(NamedTuple):
    """One compute as every lane's phase sees it."""

    kernel_names: Sequence[str]
    params: Sequence[ClArray]
    compute_id: int
    local_range: int
    global_range: int
    pipeline: bool
    blobs: int
    pipeline_type: int
    value_args: object
    write_all_owner: dict


class Crossing(NamedTuple):
    """:func:`classify`'s answer, by parameter position."""

    up: tuple
    back: tuple
    # the phase's partition-transfer bytes: the autotuner's key
    key_bytes: int


def classify(program: KernelProgram, kernel_names, params, w: Worker,
             offset: int, size: int, *, cut: bool, single: bool,
             enqueue: bool, owners: dict) -> Crossing:
    """Which bytes cross before and after lane ``w``'s phase over work
    items ``[offset, offset + size)``.  ``cut``: the engine cuts the range
    into parts (chunks, blobs) that overlap with the launches; ``single``:
    the scheduler has one lane; ``enqueue``: enqueue mode; ``owners``: the
    ``write_all`` owner by position.  Must run BEFORE the phase's uploads —
    they change coverage.

    ``up`` — an array the kernels read (``read`` and not ``write_only``):

    - ``None``: enqueue mode, and the lane holds what its launch reads
      (:func:`~.window.holds`): data lives in HBM across enqueued computes;
    - ``WHOLE``: not ``partial_read`` (the kernel may read outside the
      lane's range: it must land whole before any launch); a
      ``partial_read`` array of a single lane that moves its range in one
      piece (the range IS the array, a slice saves nothing); and, where the
      engine cuts, one that some kernel stores to OUTSIDE the work item's
      own elements (``roaming_stores``: a part uploaded behind the launch
      that scattered into it would bury the store);
    - ``PART``: every other ``partial_read`` array: the lane's range, part
      by part where the engine cuts;
    - ``ENSURE``: not read: a buffer must exist.

    ``back`` — an array the kernels write (``write`` and not
    ``read_only``), from the lane that reads it back alone
    (:func:`~.window.reads_back`: a ``write_all`` array from its owner):

    - ``DEFER``: enqueue mode: one deferred-readback record, whatever the
      engine (the flush cuts the drain itself);
    - ``OWNER``: ``write_all``: the whole array after the last launch;
    - ``LATE``: the engine cuts and the array takes roaming stores: the
      lane's range after the last launch (a part read back before a later
      launch's store would miss it);
    - ``PART``: the lane's range, part by part where the engine cuts.

    ``key_bytes`` counts the bytes that move by the lane's range (uploads
    of ``partial_read`` arrays not resident, immediate ranged readbacks):
    the ONE formula both the autotuner's ``choose`` key and its
    ``observe`` key ride (two formulas would land the measuring run's
    observation in a different power-of-two bucket than the lookup,
    leaving the key in a perpetual measuring run and the streamed path
    silently dead).  Whole-array uploads are not partition transfers;
    enqueue-mode readbacks are the flush's business."""
    roam = program.roaming_stores(
        tuple(kernel_names),
        tuple(p.flags.elements_per_work_item for p in params)) if cut else ()
    up, back, nbytes = [], [], 0
    for idx, p in enumerate(params):
        fl = p.flags
        ranged = fl.elements_per_work_item * size * p.host().dtype.itemsize
        how = ENSURE
        if fl.read and not fl.write_only:
            if enqueue and holds(w, p, offset, size):
                how = None
            elif not fl.partial_read:
                how = WHOLE
            else:
                nbytes += ranged
                how = PART if (idx not in roam if cut else not single) \
                    else WHOLE
        up.append(how)
        how = None
        if reads_back(fl, w.index, owners.get(idx)):
            if enqueue:
                how = DEFER
            elif fl.write_all:
                how = OWNER
            else:
                nbytes += ranged
                how = LATE if idx in roam else PART
        back.append(how)
    return Crossing(tuple(up), tuple(back), nbytes)


def tuner_kernel_key(kernel_names, value_args) -> tuple:
    """The autotuner's per-compute kernel key: the kernel names PLUS
    the value-arg signature — runtime values change the kernel's
    compute time (an iteration-count value is the common case), and
    a key that ignored them would reuse a stale C estimate across a
    100x compute change with no re-measure.  Dict-shaped values
    (per-kernel maps, Worker.launch) key on sorted items — tuple()
    of a dict keeps only the NAMES and would collapse a 100x value
    change into one key.  Unhashable values (array-valued args)
    degrade to the names alone."""
    try:
        if isinstance(value_args, dict):
            vkey = tuple(sorted(value_args.items()))
        else:
            vkey = tuple(value_args) if value_args else ()
        key = (tuple(kernel_names), vkey)
        hash(key)
        return key
    except TypeError:
        return (tuple(kernel_names), None)


def _picked(params, hows, how) -> list:
    return [p for p, h in zip(params, hows) if h is how]


class Phases:
    """The four engines of one scheduler.  ``window`` takes the
    deferred-readback records."""

    def __init__(self, settings, program: KernelProgram, window: Window,
                 health: HealthMonitor, single: bool):
        self.settings = settings
        self.program = program
        self.window = window
        self.health = health
        self.single = single
        self.transfer_tuner = TransferTuner()
        # per-lane chunk count of the last streamed phase (the autotuner's
        # live choice; also exported as the ck_stream_chunk_count gauge).
        # Written on the phase thread under the worker lock; readers
        # (workloads reporting, /statusz) take no lock by design — a
        # one-phase-stale chunk count is reporting, not a decision input.
        # ckcheck: ok reporting-only reads; one-slot-per-lane, stale tolerated
        self.last_stream_chunks: dict[int, int] = {}

    def _classify(self, w: Worker, job: Job, offset: int, size: int,
                  cut: bool) -> Crossing:
        return classify(
            self.program, job.kernel_names, job.params, w, offset, size,
            cut=cut, single=self.single,
            enqueue=self.settings.enqueue_mode, owners=job.write_all_owner)

    def run(self, w: Worker, job: Job, offset: int, size: int,
            plan=None) -> None:
        """Lane ``w``'s phase, under its lock (the caller holds it).
        ``plan``: the lane's part of an exchange (its kernels read across
        lanes, ``core/exchange.py``): such a phase never streams — a
        chunk's launch would read the rows of the chunk behind it before
        they were uploaded."""
        s = self.settings
        w.start_bench(job.compute_id)
        try:
            if job.pipeline and job.blobs > 1:
                (self._event if job.pipeline_type == PIPELINE_EVENT
                 else self._driver)(w, job, offset, size)
                return
            # key_bytes None: streaming is off or cannot apply — then the
            # tuner neither measures nor observes: the phase can never
            # stream, and with the kill switch off the monolithic path
            # must not pay the tuner lock at all
            key_bytes = tuner_key = None
            step = job.local_range
            if (plan is None and s.streamed_transfers
                    and not s.no_compute_mode and s.repeat_count <= 1
                    and not s.repeat_sync_kernel
                    and step > 0 and size // step >= 2):
                moves = self._classify(w, job, offset, size, cut=True)
                # else nothing to overlap — the monolithic path is exact
                if PART in moves.up or PART in moves.back:
                    key_bytes = moves.key_bytes
                    tuner_key = tuner_kernel_key(
                        job.kernel_names, job.value_args)
                    chunks = self._choose_chunks(
                        w, job, tuner_key, key_bytes, size // step)
                    if chunks > 1:
                        self._streamed(w, job, offset, size, moves, chunks,
                                       tuner_key)
                        return
            self._monolithic(w, job, offset, size, plan, key_bytes,
                             tuner_key)
        finally:
            w.end_bench(job.compute_id)

    # -- shared by the engines -----------------------------------------------
    def _up_front(self, w: Worker, job: Job, moves: Crossing) -> None:
        """What a cutting engine uploads before its first part."""
        for p, how in zip(job.params, moves.up):
            if how is WHOLE:
                w.upload(p, 0, p.size, True)
            elif how is ENSURE:
                w.ensure_resident(p)

    def _read_back(self, w: Worker, job: Job, offset: int, size: int,
                   moves: Crossing, handles: list, cut: bool) -> float:
        """What follows the last launch: the deferred records, the
        ``write_all`` owner's array, the ranges that were not read back
        part by part; then join every readback in flight.  Returns the
        seconds the join took."""
        for p, how in zip(job.params, moves.back):
            if how is DEFER:
                self.window.defer_readback(w, p, offset, size, job.compute_id)
            elif how is OWNER:
                handles.append(w.download_async(p, 0, p.size, True))
            elif how is PART and not cut:
                epw = p.flags.elements_per_work_item
                # full (no-slice) download only when the range covers the
                # whole array — else it would overwrite host elements the
                # kernel never touched
                full = (self.single and offset == 0 and size * epw == p.size
                        and not any(q.flags.partial_read for q in job.params))
                handles.append(
                    w.download_async(p, offset * epw, size * epw, full))
        for p in _picked(job.params, moves.back, LATE):
            epw = p.flags.elements_per_work_item
            handles.append(
                w.download_async(p, offset * epw, size * epw, False))
        t0d = time.perf_counter()
        for h in handles:
            Worker.finish_download(h)
        return time.perf_counter() - t0d if handles else 0.0

    def _launch(self, w: Worker, job: Job, offset: int, size: int,
                reach: str = "") -> None:
        w.launch(
            self.program, job.kernel_names, job.params, job.value_args,
            offset, size, job.local_range, job.global_range, job.local_range,
            repeats=self.settings.repeat_count,
            sync_kernel=self.settings.repeat_sync_kernel,
            compute_id=job.compute_id, reach=reach,
        )

    # -- the monolithic engine -----------------------------------------------
    def _monolithic(self, w: Worker, job: Job, offset: int, size: int,
                    plan, key_bytes: int | None, tuner_key) -> None:
        s = self.settings
        t_phase0 = time.perf_counter()
        # the tuner's MEASURING run (first contact for this key):
        # pay one fence after the launches so the wall splits into
        # honest phases — without it the async launches retire
        # inside the D2H timing window and C lands in D, leaving
        # the model a (U, ~0, C+D) estimate that under-chunks
        measuring = (
            tuner_key is not None
            and not s.no_compute_mode
            and not self.transfer_tuner.has_obs(w.index, tuner_key, key_bytes)
        )
        moves = self._classify(w, job, offset, size, cut=False)
        # H2D — t_up_stream times only the CHUNK-STREAMABLE uploads
        # (partial_read partitions, the ones key_bytes counts):
        # whole-array uploads of non-partial operands are serial in the
        # streamed path too (up-front, un-hideable), so their wall must
        # land in the tuner's C, not its U — a U inflated by un-hideable
        # bytes over-credits chunking and mis-learns every lane's
        # per-chunk overhead
        t_up = t_up_stream = 0.0
        if plan is not None:
            t_up = plan.make_current(w, job.params, job.compute_id,
                                     s.enqueue_mode)
        else:
            for p, how in zip(job.params, moves.up):
                if how is ENSURE:
                    w.ensure_resident(p)
                elif how is not None:
                    epw = p.flags.elements_per_work_item
                    t0u = time.perf_counter()
                    w.upload(p, offset * epw, size * epw, how is WHOLE)
                    dt_u = time.perf_counter() - t0u
                    t_up += dt_u
                    if p.flags.partial_read:
                        t_up_stream += dt_u
        if not s.no_compute_mode:
            if plan is not None and s.enqueue_mode and s.fused_dispatch:
                # a window's compute that could not be deferred rides
                # the ladder executable all the same, one pass of it:
                # ONE dispatch a lane whatever the rungs of its range,
                # stores written into the lane's buffers in place
                # (launch_fused falls back to the per-rung loop where
                # the values do not hash)
                w.launch_fused(
                    self.program, job.kernel_names, job.params,
                    job.value_args, offset, size, job.local_range,
                    job.global_range, job.local_range, 1,
                    compute_id=job.compute_id, reach=plan.reach,
                )
            else:
                self._launch(w, job, offset, size,
                             plan.reach if plan is not None else "")
            if measuring:
                w.fence()
        t_down = self._read_back(w, job, offset, size, moves, [], cut=False)
        self._note_transfer(
            w, tuner_key, job.compute_id, key_bytes or 0, t_up, t_down,
            time.perf_counter() - t_phase0, fenced=measuring,
            u_tune_s=t_up_stream,
        )

    def _note_transfer(
        self, w: Worker, tuner_key, compute_id: int, nbytes: int,
        u_s: float, d_s: float, wall_s: float, chunks: int = 1,
        fenced: bool = False, u_tune_s: float | None = None,
    ) -> None:
        """Record one phase's measured transfer split: the per-cid
        transfer bench (telemetry here — in immediate paths it is a
        subset of the same wall the compute bench carries, so the
        balancer floor binds at the enqueue FLUSH drain, see
        ``Sync.finish_deferred``), and (when the phase was a streaming
        candidate — ``tuner_key`` not None — and moved partition bytes)
        a tuner observation: FENCED monolithic runs teach the model its
        honest U/C/D for this (lane, kernel+values, bytes) point,
        unfenced ones only clamp (their async launches retire inside the
        D2H window, so the split is contaminated), chunked runs refine
        the lane's real per-chunk overhead.  ``nbytes`` is the
        ``key_bytes`` of the SAME phase.  ``u_tune_s`` restricts the
        tuner's U to the CHUNK-STREAMABLE uploads when the phase also
        moved whole-array operands (those are serial in the streamed path
        too — their wall belongs in C); the balancer floor keeps the
        TOTAL u_s."""
        u_ms, d_ms = u_s * 1000.0, d_s * 1000.0
        if not self.settings.enqueue_mode:
            # the IMMEDIATE path alone: one call = one iteration, so the
            # phase wall is already on the per-iteration scale of the
            # health signal and of the per-call compute bench.  In
            # enqueue mode the flush drain owns both (its values are
            # divided by the window's count, ``Sync.finish_deferred``):
            # an in-window phase wall is per-WINDOW scaled (a
            # post-coverage-reset phase re-uploads the whole partition
            # once for N iterations) and steady covered phases are 0.0 —
            # either write would corrupt the baseline and the floor the
            # next rebalance reads
            if u_s + d_s > 0.0:  # only phases that MOVED bytes
                self.health.observe(w.index, "transfer", u_s + d_s)
            w.transfer_benchmarks[compute_id] = u_ms + d_ms
        tune_u_ms = u_ms if u_tune_s is None else u_tune_s * 1000.0
        if tuner_key is not None and nbytes > 0 and (
                tune_u_ms > 0.0 or d_ms > 0.0):
            c_ms = max(wall_s * 1000.0 - tune_u_ms - d_ms, 0.0)
            _tu = TRACER.t0("tune")
            self.transfer_tuner.observe(
                w.index, tuner_key, nbytes, tune_u_ms, c_ms, d_ms,
                chunks=chunks, wall_ms=wall_s * 1000.0, fenced=fenced,
            )
            if _tu:
                TRACER.record("tune", _tu, cid=compute_id, lane=w.index,
                              tag=f"observe:{chunks}")

    # -- the STREAM engine ---------------------------------------------------
    def _choose_chunks(self, w: Worker, job: Job, tuner_key, nbytes: int,
                       max_chunks: int) -> int:
        """The lane's chunk count for this phase: the pin, else the
        autotuner's choice."""
        chunks = self.settings.stream_chunks
        if not chunks:
            _tu = TRACER.t0("tune")
            chunks = self.transfer_tuner.choose(
                w.index, tuner_key, nbytes, max_chunks)
            if _tu:
                TRACER.record("tune", _tu, cid=job.compute_id, lane=w.index,
                              tag=f"choose:{chunks}")
        chunks = min(max(int(chunks), 1), max_chunks)
        # record the live choice even when it is "monolithic" — an
        # artifact saying chunks=1 ("the autotuner judged chunk overhead
        # to outweigh overlap on this lane") beats a stale count
        was = self.last_stream_chunks.get(w.index)
        if was != chunks:
            # flight-record the DECISION, not the steady state: only a
            # changed chunk count is an autotuner move worth a ring slot
            FLIGHT.event("stream-choice", lane=w.index, chunks=chunks,
                         nbytes=nbytes)
            if TRACER.active():
                TRACER.instant("tune", cid=job.compute_id, lane=w.index,
                               tag=f"chunks:{was}->{chunks}")
        self.last_stream_chunks[w.index] = chunks
        w.m_chunk_count.set(chunks)
        return chunks

    def _streamed(self, w: Worker, job: Job, offset: int, size: int,
                  moves: Crossing, chunks: int, tuner_key) -> None:
        """The lane's timeline becomes a true read/compute/write pipeline:
        the CALLER thread is the transfer lane — it stages chunk j's H2D
        (the DMA starts immediately) and submits chunk j's closure
        (commit + ladder launch + D2H issue) to the per-worker stream
        driver, whose depth bounds how far staging runs ahead of dispatch.
        The kernel sequence stays KERNEL-MAJOR exactly like
        ``Worker.launch`` (kernel k covers the whole range, ascending
        offsets, before kernel k+1), so results are bit-identical to the
        monolithic path — the only thing that moves is WHEN transfers are
        issued.  Uploads interleave with the FIRST kernel's chunk
        launches, downloads with the LAST kernel's (one kernel: both in
        one wavefront); middle kernels launch whole-range.

        Runs under the worker's phase lock, which is why the
        stream-driver closures never take worker locks — see
        ``Worker.stream_dispatch_async``."""
        params = job.params
        up_parts = _picked(params, moves.up, PART)
        down_parts = _picked(params, moves.back, PART)
        plan = chunk_plan(size, job.local_range, chunks)
        _tt = TRACER.t0("pipeline-stage")
        t_phase0 = time.perf_counter()
        self._up_front(w, job, moves)
        handles: list = []
        stage_s = 0.0
        stall_s = 0.0   # backpressure waits in stream_dispatch_async
        n_submits = 0   # the stall normalizer: actual submits made
        depth = max(1, int(self.settings.stream_queue_depth))
        last = len(job.kernel_names) - 1

        def launch(name, off, n):
            w.launch(
                self.program, [name], params, job.value_args, off, n,
                job.local_range, job.global_range, job.local_range,
                compute_id=job.compute_id,
            )

        try:
            for ki, name in enumerate(job.kernel_names):
                do_up = bool(up_parts) and ki == 0
                do_down = bool(down_parts) and ki == last
                if not do_up and not do_down:
                    # middle kernels: plain whole-range ladder (nothing
                    # to overlap with — operands are already resident)
                    launch(name, offset, size)
                    continue
                for coff, csz in plan:
                    boff = offset + coff
                    staged: list = []
                    if do_up:
                        t0s = time.perf_counter()
                        staged = [
                            w.stage_upload_chunk(
                                p,
                                boff * p.flags.elements_per_work_item,
                                csz * p.flags.elements_per_work_item,
                            )
                            for p in up_parts
                        ]
                        stage_s += time.perf_counter() - t0s

                    def run_chunk(
                        name=name, boff=boff, csz=csz, staged=staged,
                        do_down=do_down,
                    ):
                        for s in staged:
                            w.commit_upload(s)
                        launch(name, boff, csz)
                        if do_down:
                            for p in down_parts:
                                epw = p.flags.elements_per_work_item
                                handles.append(
                                    w.download_chunk_async(
                                        p, boff * epw, csz * epw
                                    )
                                )

                    t0q = time.perf_counter()
                    # inside a preflighted batch iteration the armed
                    # driver-submit point already fired for every lane
                    # BEFORE anything dispatched (compute_fused_batch);
                    # firing again mid-phase would be a dirty cross-lane
                    # failure containment could not repair
                    w.stream_dispatch_async(
                        run_chunk, depth,
                        preflighted=self.window.batch_preflighted)
                    stall_s += time.perf_counter() - t0q
                    n_submits += 1
                w.drain_stream_dispatch()
        except BaseException:
            # closures must never outlive the phase lock the caller
            # holds; the primary error outranks any drain follow-up
            try:
                w.drain_stream_dispatch()
            except Exception:  # noqa: BLE001 - primary error wins
                pass
            raise
        t_down = self._read_back(w, job, offset, size, moves, handles,
                                 cut=True)
        self._note_transfer(
            w, tuner_key, job.compute_id, moves.key_bytes, stage_s, t_down,
            time.perf_counter() - t_phase0, chunks=len(plan),
        )
        # stream-driver backpressure: time the caller thread spent
        # BLOCKED in submit because the double buffer was full — the
        # lane-health signal for "this lane's dispatch cannot keep up
        # with staging" (a degrading lane stalls its feeder first).
        # PER SUBMIT, the same normalization rule as the fence/transfer
        # signals: a retune from 4 to 16 chunks — or a 1-kernel ladder
        # becoming a 2-kernel one (up-loop + down-loop submit the chunk
        # plan twice) — scales the raw per-phase sum with identical
        # per-submit health, and the un-normalized feed would read as
        # lane degradation
        self.health.observe(
            w.index, "stream_stall", stall_s / max(1, n_submits))
        TRACER.record(
            "pipeline-stage", _tt, cid=job.compute_id, lane=w.index,
            tag=f"STREAM x{len(plan)}",
        )

    # -- the pipelined engines -----------------------------------------------
    def _blobs(self, w: Worker, job: Job, offset: int, size: int) -> tuple:
        """What both engines start from: the blob geometry, what goes up
        before the first blob, and the arrays that cross blob by blob."""
        blobs = job.blobs
        blob = size // blobs
        if blob <= 0:
            blob, blobs = size, 1
        moves = self._classify(w, job, offset, size, cut=True)
        self._up_front(w, job, moves)
        return (blob, blobs, moves, _picked(job.params, moves.up, PART),
                _picked(job.params, moves.back, PART))

    def _driver(self, w: Worker, job: Job, offset: int, size: int) -> None:
        """DRIVER engine: depth-first dispatch chains — blob k's full
        H2D → compute → D2H is issued back-to-back with no host
        synchronization, blob k+1's chain follows immediately (reference:
        the driver-driven 16-queue pipeline, blob k → queue k mod 16 doing
        R+C+W with no events, Cores.cs:1371-1858).  XLA's async dispatch
        streams play the role of the 16 in-order queues: the transfer
        engine runs blob k+1's DMA while the compute stream runs blob k."""
        _tt = TRACER.t0("pipeline-stage")
        blob, blobs, moves, up_parts, down_parts = self._blobs(
            w, job, offset, size)
        handles: list = []
        for k in range(blobs):
            boff = offset + k * blob
            for p in up_parts:
                epw = p.flags.elements_per_work_item
                w.upload(p, boff * epw, blob * epw, False)
            if not self.settings.no_compute_mode:
                self._launch(w, job, boff, blob)
            for p in down_parts:
                epw = p.flags.elements_per_work_item
                handles.append(
                    w.download_async(p, boff * epw, blob * epw, False))
        self._read_back(w, job, offset, size, moves, handles, cut=True)
        TRACER.record(
            "pipeline-stage", _tt, cid=job.compute_id, lane=w.index,
            tag=f"DRIVER x{blobs}",
        )

    def _event(self, w: Worker, job: Job, offset: int, size: int) -> None:
        """EVENT engine: breadth-first 3-stage wavefront with a
        configurable read lookahead L (``pipeline_lookahead``, default 2) —
        at step j the host *stages* blob j's H2D DMA (transfer starts
        immediately, no device-side insert yet), *commits + computes* blob
        j-L, and starts blob j-L-1's D2H (reference: the event-driven
        3-queue pipeline whose read/compute/write queues chain per-blob
        events, Cores.cs:1236-1367).  Explicit dependency chaining: the
        commit (dynamic_update_slice of the staged slice) is the
        device-side edge from the read stage into the compute stage, so
        blob j's DMA always has L compute-steps of latency to hide behind
        — a deeper lookahead keeps the inbound DMA lane busy even when a
        single blob's transfer outlasts one compute step (the r3 overlap
        shortfall), at the cost of up to L+1 simultaneously staged blobs
        of host/HBM footprint (blob j is staged before blob j-L pops)."""
        _tt = TRACER.t0("pipeline-stage")
        blob, blobs, moves, up_parts, down_parts = self._blobs(
            w, job, offset, size)
        look = max(1, int(self.settings.pipeline_lookahead))
        staged: dict[int, list] = {}
        handles: list = []
        for j in range(blobs + look + 1):
            if j < blobs:  # read stage: start blob j's DMA
                boff = offset + j * blob
                staged[j] = [
                    w.stage_upload(
                        p,
                        boff * p.flags.elements_per_work_item,
                        blob * p.flags.elements_per_work_item,
                    )
                    for p in up_parts
                ]
            k = j - look
            if 0 <= k < blobs:  # compute stage: commit blob k, launch kernels
                for s in staged.pop(k, ()):
                    w.commit_upload(s)
                if not self.settings.no_compute_mode:
                    self._launch(w, job, offset + k * blob, blob)
            m = j - look - 1
            if 0 <= m < blobs:  # write stage
                boff = offset + m * blob
                for p in down_parts:
                    epw = p.flags.elements_per_work_item
                    handles.append(
                        w.download_async(p, boff * epw, blob * epw, False))
        self._read_back(w, job, offset, size, moves, handles, cut=True)
        TRACER.record(
            "pipeline-stage", _tt, cid=job.compute_id, lane=w.index,
            tag=f"EVENT x{blobs} look{look}",
        )
