"""Worker — one dispatch lane per TPU chip.

TPU-native analogue of the reference's per-device ``Worker``
(Worker.cs): owns the chip's buffer cache (the reference's
``Dictionary<object, ClBuffer>`` keyed by array object, Worker.cs:194,
576-720), runs H2D → launch → D2H for its assigned sub-range of the global
work-item range, and keeps per-compute-id wall-time benchmarks that feed the
load balancer (Worker.cs:753-807).

The reference's 21 command queues become XLA async dispatch: every
``device_put`` / launch / ``copy_to_host_async`` is an asynchronous
operation on the chip's stream; blob-chunked launches overlap transfers with
compute without explicit queue juggling (core/cores.py drives that).

Launch geometry: a chip's quantized range is covered by a *binary ladder* of
chunk sizes (``step·2^k``), so every geometry the balancer can produce
compiles at most ``O(log(range/step))`` distinct XLA executables — the
re-balancer never causes unbounded recompilation (the reference relies on
NDRange offsets being launch parameters; ours are runtime scalars too).
"""

from __future__ import annotations

import queue
import threading
import time
import weakref
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..arrays.clarray import ClArray
from ..kernel.registry import KernelProgram, lowering_meta
from ..metrics.registry import REGISTRY
from ..obs.flight import FLIGHT
from ..trace.device import MARKS
from ..trace.spans import TRACER
from ..utils.faultinject import FAULTS
from ..utils.markers import MarkerCounter

__all__ = ["Worker"]


def _native_lib():
    from ..native import load

    return load()


@partial(jax.jit, static_argnums=(2,))
def _slice_out(buf, off, size: int):
    return lax.dynamic_slice(buf, (jnp.asarray(off, jnp.int32),), (size,))


#: bytes a piece of the flush's batched read-back holds: the pieces are the
#: chunked drain's chunks (a piece's host copy overlaps the next pieces'
#: crossing), and ONE length for every split is what lets the executables a
#: warm process holds serve whatever the balancer chooses.  4 MiB: a piece
#: costs the link ~0.45 ms before its first byte, so four lanes' 16 MiB are
#: on the host in 4.5 ms in pieces of 4 MiB against 6.1 in pieces of 1 MiB
#: (PERF.md s.6, PR 42), and a whole piece takes finish_download's native
#: parallel copy
PIECE_BYTES = 4 << 20


@partial(jax.jit, static_argnums=(2, 3))
def _slice_pieces(bufs, offs, counts: tuple, lengths: tuple):
    """Every piece of a lane's flush in ONE program: ``counts[j]`` pieces
    of ``lengths[j]`` elements each out of ``bufs[j]``, at the run-time
    offsets ``offs`` (one ``int32`` vector for all of them, numpy handed
    to the call: no Python scalar crosses).  Only the buffers' shapes, the
    counts and the lengths key the executable; a split never does."""
    out, at = [], 0
    for buf, k, c in zip(bufs, counts, lengths):
        out.append(tuple(
            lax.dynamic_slice(buf, (offs[at + i],), (c,)) for i in range(k)))
        at += k
    return tuple(out)


@jax.jit
def _update_slice(buf, sl, off):
    return lax.dynamic_update_slice(buf, sl, (jnp.asarray(off, jnp.int32),))


# the same update written INTO the buffer it is given: a strip of a few
# rows laid into a lane's full-size buffer must not copy the buffer
_update_slice_donating = jax.jit(
    _update_slice.__wrapped__, donate_argnums=(0,))


def _ladder(size: int, step: int) -> list[int]:
    """Decompose ``size`` (a multiple of ``step``) into descending
    ``step·2^k`` chunks — the compile-once launch ladder."""
    out: list[int] = []
    units = size // step
    bit = 1 << (units.bit_length() - 1) if units else 0
    while units:
        if bit <= units:
            out.append(bit * step)
            units -= bit
        bit >>= 1
    return out


def launch_ladder(size: int, step: int) -> list[int]:
    """The ladder-build seam: the ONE decomposition every launch-geometry
    consumer shares — per-call dispatch (:meth:`Worker.launch`, which
    walks it rung by rung, or hands a ladder of more than one rung to
    the fused executable that predicates the same rungs on the bits of
    ``size // step``: ``KernelProgram.fused_launcher``), the
    streamed chunk planner (``core/stream.chunk_plan``), and the
    persistent executable cache's key/warmup geometry
    (``core/compilecache``).  A second decomposition would silently warm
    and key executables the live path never launches."""
    return _ladder(size, step)


class _DriverQueue:
    """Depth-limited per-device dispatch driver (the fused-iteration
    path's host-side queue, core/cores.py): ONE daemon thread per chip
    executes submitted dispatch closures strictly FIFO, so host-side
    dispatch of device B's ladder overlaps device A's execution while
    per-device ordering stays exact (a thread pool starts tasks in
    submission order but two tasks for one device can still race on lock
    acquisition).

    ``depth`` (per :meth:`submit`, so a runtime retune of the caller's
    knob takes effect immediately) bounds the in-flight closures (queued
    + executing): a host running far ahead of device dispatch blocks in
    :meth:`submit` — backpressure, not unbounded growth.  Closure
    failures are held and re-raised at the next :meth:`drain` or
    :meth:`submit` (a failed fused dispatch must surface at the window's
    sync point, never masquerade as fast device work — the barrier()
    error contract)."""

    def __init__(self, depth_gauge=None, name: str = "driver",
                 lane: int | None = None):
        self._q: queue.Queue = queue.Queue()
        self.lane = lane  # fault-point selector (utils/faultinject.py)
        self._cond = threading.Condition()
        self._errors: list[Exception] = []
        self._pending = 0
        # driver-FIFO occupancy gauge (metrics registry): queued +
        # executing closures, the fused path's host-side backlog
        self._depth_gauge = depth_gauge
        self.name = name  # observability: which lane's which driver
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def preflight(self) -> None:
        """Run the submit-time failure checks WITHOUT queuing anything:
        the armed ``driver-submit`` fault point and any pending closure
        error both raise HERE.  The fused batch dispatch preflights
        EVERY lane before queuing ANY lane's closure
        (``Window._dispatch``), so a fault fired at this stage
        leaves device iteration counts undiverged — the serving tier's
        blast-radius containment can re-dispatch the residue bit-exactly
        (``FusedBatchError.clean``).

        The CLEAN marker is stamped HERE, per raise source: only the
        injected fault is clean (it fired before anything was queued).
        A pending error popped from the queue belongs to an EARLIER
        closure — that closure's work never applied on this lane while
        its iterations may already be counted applied, so re-dispatch
        could silently corrupt: explicitly NOT clean."""
        if FAULTS.enabled:
            try:
                FAULTS.raise_if_fired("driver-submit", lane=self.lane,
                                      where=self.name)
            except Exception as e:  # noqa: BLE001 - marker, re-raised
                e._ck_clean_window = True
                raise
        with self._cond:
            if self._errors:
                e = self._errors[0]
                self._errors.clear()
                e._ck_clean_window = False
                raise e

    def submit(self, fn: Callable[[], None], depth: int = 2,
               preflighted: bool = False) -> None:
        if FAULTS.enabled and not preflighted:
            # chaos plane (utils/faultinject.py): an armed driver-submit
            # clause makes THIS submit raise InjectedFaultError — the
            # fused window poisons and the error surfaces at the sync
            # point, exactly like a real dispatch failure.  A caller
            # that already ran :meth:`preflight` skips the fire so one
            # dispatch costs the clause exactly one counted hit per
            # lane either way (the determinism contract).
            FAULTS.raise_if_fired("driver-submit", lane=self.lane,
                                  where=self.name)
        with self._cond:
            if self._errors:
                e = self._errors[0]
                self._errors.clear()
                raise e
            while self._pending >= max(1, int(depth)):
                # bounded wait + loop re-check: a submit parked on
                # backpressure must not hang forever if the driver
                # thread died (pending would then never drain).  The
                # liveness check applies only while STILL blocked — a
                # clean close() that drained the backlog and exited
                # must not be misreported as a thread death
                self._cond.wait(1.0)
                if self._pending >= max(1, int(depth)) and \
                        not self._thread.is_alive():
                    raise RuntimeError(
                        f"driver {self.name!r} thread died with "
                        f"{self._pending} closure(s) pending")
            self._pending += 1
            if self._depth_gauge is not None:
                self._depth_gauge.set(self._pending)
        self._q.put(TRACER.bind(fn, self.lane))

    def _run(self) -> None:
        while True:
            # ckcheck: ok sentinel-terminated daemon loop — close()
            # always enqueues the None sentinel; an unbounded get IS
            # the idle state of this thread
            fn = self._q.get()
            if fn is None:
                return
            try:
                fn()
            except Exception as e:  # noqa: BLE001 - re-raised at drain
                # capture FIRST — the error contract (surfacing at the
                # next submit/drain) outranks observability, and a
                # broken __str__ in the instrumentation below must
                # neither drop the error nor kill this daemon thread
                # (a dead driver thread hangs every later drain)
                with self._cond:
                    self._errors.append(e)
                try:
                    # observe the failure so the black box already holds
                    # it when the caller's sync point re-raises and
                    # triggers the postmortem dump
                    FLIGHT.event(
                        "driver-error", driver=self.name,
                        exc_type=type(e).__name__, exc=str(e)[:500],
                    )
                    TRACER.instant("driver-error", tag=f"{self.name}: {e}")
                except Exception:  # noqa: BLE001 - observing is optional
                    pass
            finally:
                with self._cond:
                    self._pending -= 1
                    if self._depth_gauge is not None:
                        self._depth_gauge.set(self._pending)
                    self._cond.notify_all()

    def drain(self) -> None:
        """Block until every submitted closure has RUN (host-side
        dispatch complete; device completion is the fence's business),
        re-raising the first failure."""
        with self._cond:
            while self._pending > 0:
                # bounded wait + loop re-check: a drain must not hang
                # shutdown forever if the driver thread died mid-batch
                # (the pending count would then never reach zero)
                self._cond.wait(1.0)
                if self._pending > 0 and not self._thread.is_alive():
                    raise RuntimeError(
                        f"driver {self.name!r} thread died with "
                        f"{self._pending} closure(s) pending")
            if self._errors:
                e = self._errors[0]
                self._errors.clear()
                raise e

    def close(self) -> None:
        self._q.put(None)
        self._thread.join(timeout=5.0)


class Worker:
    """Per-chip execution engine."""

    def __init__(self, device: jax.Device, index: int):
        self.device = device
        self.index = index
        # serializes whole lane phases when several host threads drive
        # DIFFERENT compute ids through one Cores concurrently (the
        # reference's kernelWithId clones kernels per (name, computeId)
        # for exactly this, Worker.cs:291-316, and wraps worker phases in
        # lock(workers[i]), Cores.cs:751,779,826).  _buffers/_uploaded are
        # read-modify-write sequences per array key — unserialized, two
        # compute ids touching one array lose updates, and fence() would
        # iterate the dict while another lane inserts.
        self.lock = threading.RLock()
        # array-object → device buffer (reference: Worker.cs:194).
        # Buffer/coverage state is guarded by PROTOCOL, not by a lock the
        # analyzer can see: while a phase holds self.lock, either the
        # phase thread mutates these dicts directly, or it delegates to
        # the stream/fused driver thread and BLOCKS (stage/submit
        # backpressure, drain) without touching them — single writer at
        # every instant, see stream_dispatch_async
        # ckcheck: ok single-writer stream/fused driver protocol
        self._buffers: dict[int, Any] = {}
        # ckcheck: ok single-writer stream/fused driver protocol
        self._buffer_owner: dict[int, ClArray] = {}  # strong refs, like the reference
        # array-object → (offset, size) element range this chip has uploaded;
        # enqueue mode skips a re-upload only when the requested range is
        # covered — so the balancer may MOVE ranges between syncs and the
        # newly-acquired region is fetched instead of silently served stale
        # ckcheck: ok single-writer stream/fused driver protocol
        self._uploaded: dict[int, tuple[int, int]] = {}
        # per-compute-id accumulated wall ms (reference: Worker.cs:190,753-807)
        self.benchmarks: dict[int, float] = {}
        self._bench_t0: dict[int, float] = {}
        # per-compute-id TRANSFER wall ms, measured separately from the
        # phase wall: per-phase H2D staging + D2H materialization in the
        # immediate paths (telemetry — a subset of the same wall the
        # compute bench carries), and the lane's share of the enqueue
        # FLUSH drain (Sync.finish_deferred — where the balancer's
        # transfer floor genuinely binds: steady-state enqueue benches
        # exclude transfers entirely).  Fed into
        # core/balance.load_balance(transfer_ms=...) so lanes with
        # unequal effective link bandwidth stop getting equal shares.
        self.transfer_benchmarks: dict[int, float] = {}
        # last H2D transfer path taken ("dlpack-zero-copy" | "dlpack+move" |
        # "staged-dma") — observability for the zero_copy flag
        self.last_upload_path: str | None = None
        # fine-grained progress markers (reference: queue markers,
        # ClCommandQueue.cs:99-115); None unless enabled by the cruncher —
        # toggled only while the lane is quiescent (no phase in flight),
        # the fine_grained_queue_control contract
        # ckcheck: ok toggled quiescent; MarkerCounter locks internally
        self.markers: MarkerCounter | None = None
        # per-compute-id LAST output value of the most recent launch —
        # materializing it retires exactly when that cid's final kernel
        # retires (stream order), which is what the per-cid fence split
        # probes (trace/attribution.py split_fence_benches).  Recorded
        # only while track_cid_outputs is set (Cores.fence_split
        # propagates it): each record pins a device buffer until the cid
        # cycles out, a cost only the split should pay.
        self.track_cid_outputs = False
        # launch-path writes ride the driver protocol above; barrier's
        # fence_cid reads run AFTER the drivers drained (no concurrent
        # writer), and the fence_split-off clear holds self.lock
        # ckcheck: ok single-writer driver protocol + post-drain reads
        self._cid_last_out: dict[int, Any] = {}
        # the ladder's run-time scalars kept on the device: value -> array
        self._ladder_scalars: dict[int, Any] = {}
        # array-object -> pieces the batched read-back cuts its share into
        # (download_slices_async): a power of two that only grows, so a
        # lane compiles log2 programs an array at most, in warm-up
        # ckcheck: ok single-writer: the flush issues under self.lock
        self._piece_counts: dict[int, int] = {}
        # coverage epoch: bumped by every reset_coverage().  The fused
        # dispatch path (core/cores.py) snapshots it at window engage and
        # compares one int per deferral instead of re-walking per-array
        # coverage records — any sync-point rebalance that reset this
        # chip's coverage mid-window is detected and the fused run
        # disengages instead of launching over ranges that now need a
        # re-upload (the window-scoped coverage-epoch contract).
        self.coverage_epoch = 0
        # depth-limited per-device dispatch driver (fused path); lazy —
        # workers outside the fused path never start the thread
        self._driver: _DriverQueue | None = None
        # SECOND driver for the streamed-transfer path (Phases._streamed):
        # its closures run while the submitting thread HOLDS this worker's
        # phase lock, so they must never take worker locks — sharing the
        # fused driver would let a fused closure (which does take w.lock)
        # queue ahead of a streamed closure and deadlock the drain
        self._stream_driver: _DriverQueue | None = None
        # always-on health metrics (metrics/registry.py): transfer bytes,
        # fence waits, driver occupancy — handles cached here because the
        # lane label is static for the worker's lifetime
        self._m_upload_bytes = REGISTRY.counter(
            "ck_upload_bytes_total", "H2D bytes uploaded", lane=index)
        self._m_download_bytes = REGISTRY.counter(
            "ck_download_bytes_total", "D2H bytes materialized", lane=index)
        # what WHOLE (unsplit) arrays moved: a full upload, a full or
        # ``write_all`` download; the ranged and chunked transfers are the
        # rest of the two totals above
        self._m_whole_up, self._m_whole_down = (
            REGISTRY.counter(
                "ck_whole_array_bytes_total",
                "bytes of arrays moved whole, not cut by the lane's range",
                dir=way, lane=index) for way in ("h2d", "d2h"))
        self._m_download_seconds = REGISTRY.histogram(
            "ck_download_seconds",
            "D2H issue (copy_to_host_async) to landed in host memory",
            lane=index)
        self._m_fence_waits = REGISTRY.counter(
            "ck_fence_waits_total", "whole-lane retirement fences",
            lane=index)
        self._m_fence_seconds = REGISTRY.histogram(
            "ck_fence_seconds", "fence wait duration", lane=index)
        self._m_driver_depth = REGISTRY.gauge(
            "ck_driver_queue_depth", "fused-dispatch driver FIFO occupancy",
            lane=index)
        # streamed-transfer health: chunks moved each direction, the
        # stream driver's backlog, and the autotuner's current choice
        # (Cores sets the gauge when it plans a streamed phase)
        self._m_h2d_chunks = REGISTRY.counter(
            "ck_stream_chunks_total", "streamed transfer chunks",
            dir="h2d", lane=index)
        self._m_d2h_chunks = REGISTRY.counter(
            "ck_stream_chunks_total", "streamed transfer chunks",
            dir="d2h", lane=index)
        self._m_stream_depth = REGISTRY.gauge(
            "ck_stream_queue_depth", "streamed-transfer driver FIFO occupancy",
            lane=index)
        self.m_chunk_count = REGISTRY.gauge(
            "ck_stream_chunk_count", "autotuner-chosen chunk count",
            lane=index)
        # the exchange between lanes (Cores._stage_exchange): strips this
        # lane took from the lanes that last wrote them (lay_strip)
        self._m_halo_bytes = REGISTRY.counter(
            "ck_halo_bytes_total",
            "bytes fetched from the lane that last wrote them", lane=index)
        self._m_halo_exchanges = REGISTRY.counter(
            "ck_halo_exchanges_total",
            "strips fetched from another lane's buffer", lane=index)

    # -- benchmarks ----------------------------------------------------------
    def start_bench(self, compute_id: int) -> None:
        self._bench_t0[compute_id] = time.perf_counter()

    def end_bench(self, compute_id: int) -> None:
        t0 = self._bench_t0.pop(compute_id, None)
        if t0 is not None:
            self.benchmarks[compute_id] = (time.perf_counter() - t0) * 1000.0

    # -- buffer management ---------------------------------------------------
    def _buffer_for(self, arr: ClArray) -> Any:
        key = id(arr)
        buf = self._buffers.get(key)
        host = arr.host()
        if buf is None or buf.shape[0] != host.size or buf.dtype != host.dtype:
            # allocated ON this lane's chip — zeros built on the default
            # device would land on chip 0 first and cross the interconnect
            buf = jnp.zeros(host.size, host.dtype, device=self.device)
            self._buffers[key] = buf
            self._buffer_owner[key] = arr
            self._uploaded.pop(key, None)
        return buf

    def _h2d(self, host_slice: np.ndarray, zero_copy: bool):
        """One H2D transfer (every upload path funnels here — including
        staged/streamed chunks).  With an armed ``slow-link`` fault
        clause (utils/faultinject.py) the transfer runs Nx slower: the
        injected sleep scales the measured staging wall, so the lane's
        transfer benchmarks, health baseline, and balancer floor all
        see a REAL Nx-degraded link."""
        if FAULTS.enabled:
            t0 = time.perf_counter()
            out = self._h2d_impl(host_slice, zero_copy)
            d = FAULTS.delay_s("slow-link", lane=self.index, where="h2d",
                               base_s=time.perf_counter() - t0)
            if d > 0.0:
                time.sleep(d)
            return out
        return self._h2d_impl(host_slice, zero_copy)

    def _h2d_impl(self, host_slice: np.ndarray, zero_copy: bool):
        """``zero_copy`` requests the
        ``CL_MEM_USE_HOST_PTR`` analogue (SURVEY.md §7): import the host
        buffer via dlpack — genuinely zero-copy on the CPU backend when the
        FastArr-aligned memory can be aliased — falling back to a direct
        DMA from the (page-aligned, pinned-staging) host array otherwise."""
        if zero_copy:
            try:
                x = jnp.from_dlpack(host_slice)
                if self.device in x.devices():
                    # aliased, not copied: ZERO bytes moved — counting
                    # host_slice.nbytes here would report full H2D
                    # traffic for runs that transferred nothing
                    self.last_upload_path = "dlpack-zero-copy"
                else:
                    x = jax.device_put(x, self.device)
                    self.last_upload_path = "dlpack+move"
                    self._m_upload_bytes.inc(host_slice.nbytes)
                return x
            except Exception:
                pass  # backend can't alias host memory — stage instead
        self.last_upload_path = "staged-dma"
        self._m_upload_bytes.inc(host_slice.nbytes)
        # numpy → target device directly: wrapping in jnp.asarray first
        # would land on the default device and force a cross-device copy
        return jax.device_put(host_slice, self.device)

    def upload_covers(self, arr: ClArray, offset_elems: int, size_elems: int) -> bool:
        """True iff this chip's resident data already covers the requested
        element range (the enqueue-mode residency test; range-aware so a
        rebalance between syncs forces a fetch of the moved region)."""
        rec = self._uploaded.get(id(arr))
        return (
            rec is not None
            and id(arr) in self._buffers
            and rec[0] <= offset_elems
            and offset_elems + size_elems <= rec[0] + rec[1]
        )

    def _record_upload(self, arr: ClArray, offset_elems: int, size_elems: int) -> None:
        key = id(arr)
        rec = self._uploaded.get(key)
        if rec is not None and not (
            offset_elems > rec[0] + rec[1] or rec[0] > offset_elems + size_elems
        ):
            lo = min(rec[0], offset_elems)
            hi = max(rec[0] + rec[1], offset_elems + size_elems)
            self._uploaded[key] = (lo, hi - lo)
        else:
            self._uploaded[key] = (offset_elems, size_elems)

    def upload(self, arr: ClArray, offset_elems: int, size_elems: int, full: bool) -> None:
        """H2D: full array or only this chip's range slice (reference:
        writeToBuffer / writeToBufferRanged, Worker.cs:821-885)."""
        _tt = TRACER.t0("upload")
        key = id(arr)
        host = arr.host()
        if full:
            buf = self._h2d(host, arr.flags.zero_copy)
            self._buffers[key] = buf
            self._buffer_owner[key] = arr
            self._uploaded[key] = (0, host.size)
            self._m_whole_up.inc(host.nbytes)
            if self.markers is not None:
                self.markers.add()
                self.markers.reach_when_ready(buf)
            TRACER.record("upload", _tt, lane=self.index, tag=arr.name,
                          bytes=host.nbytes)
            return
        buf = self._buffer_for(arr)
        if self.markers is not None:
            self.markers.add()
        part = host[offset_elems : offset_elems + size_elems]
        sl = self._h2d(part, arr.flags.zero_copy)
        out = _update_slice(buf, sl, offset_elems)
        self._buffers[key] = out
        self._record_upload(arr, offset_elems, size_elems)
        if self.markers is not None:
            self.markers.reach_when_ready(out)
        TRACER.record("upload", _tt, lane=self.index, tag=arr.name,
                      bytes=part.nbytes)

    def stage_upload(self, arr: ClArray, offset_elems: int, size_elems: int,
                     kind: str = "upload", settled: bool = False):
        """Start the H2D DMA for a range slice WITHOUT inserting it into the
        chip's buffer yet — the event-pipeline engine stages blob j+1's
        transfer while blob j computes (reference: the read queue of the
        3-queue event pipeline, Cores.cs:1263-1295).  Returns a handle for
        :meth:`commit_upload`.  ``kind`` names the span recorded
        (``upload-chunk`` for one ladder-aligned chunk of a streamed
        partition upload — same split as :meth:`download_async`).
        ``settled``: return only once the slice no longer depends on the
        host array — the caller is about to let OTHER lanes write their
        results into that array (``Cores._stage_exchange``).  A transfer to
        a chip may still be reading the host memory when ``device_put``
        returns, and on a host-CPU lane ``device_put`` does not copy at
        all: the "device" array IS the host memory."""
        _tt = TRACER.t0(kind)
        host = arr.host()
        if self.markers is not None:
            self.markers.add()
        part = host[offset_elems : offset_elems + size_elems]
        if settled and self.device.platform == "cpu":
            part = part.copy()
        sl = self._h2d(part, arr.flags.zero_copy and not settled)
        if settled:
            sl.block_until_ready()
        if self.markers is not None:
            self.markers.reach_when_ready(sl)
        if _tt:
            tag = (f"{arr.name}@{offset_elems}+{size_elems}"
                   if kind == "upload-chunk" else f"stage:{arr.name}")
            TRACER.record(kind, _tt, lane=self.index, tag=tag,
                          bytes=part.nbytes)
        return (arr, sl, offset_elems)

    def stage_upload_chunk(self, arr: ClArray, offset_elems: int, size_elems: int):
        """One ladder-aligned chunk of a STREAMED partition upload: the
        caller thread is the transfer lane — it stages chunk j+1 while
        the stream driver dispatches chunk j's commit+launch."""
        self._m_h2d_chunks.inc()
        return self.stage_upload(
            arr, offset_elems, size_elems, kind="upload-chunk"
        )

    def commit_upload(self, staged) -> None:
        """Insert a staged slice into the range buffer (the device-side
        dependency edge between the read queue and the compute queue)."""
        arr, sl, off = staged
        if off == 0 and sl.shape[0] == arr.host().size:
            # the whole array: the staged copy IS the lane's buffer
            self.set_buffer(arr, sl)
        else:
            buf = self._buffer_for(arr)
            self._buffers[id(arr)] = _update_slice(buf, sl, off)
        self._record_upload(arr, off, sl.shape[0])

    def cut_strip(self, arr: ClArray, offset_elems: int, size_elems: int):
        """``[offset, offset + size)`` of this lane's buffer of ``arr`` as
        it stands NOW, for another lane to take (:meth:`lay_strip`): a
        slice on this lane's own device, ordered after the launch that
        wrote the buffer and unmoved by whatever replaces it later.  The
        caller holds this lane's lock."""
        return _slice_out(self._buffers[id(arr)], offset_elems, size_elems)

    def lay_strip(self, src: "Worker", arr: ClArray, strip,
                  offset_elems: int) -> str:
        """Bring a strip that ``src``, the lane that last wrote it, cut
        from its buffer (:meth:`cut_strip`) onto this lane's device and lay
        it into this lane's buffer at ``offset``: device to device where
        both lanes sit on one platform (asynchronous: the runtime orders
        the copy after the writer's launch and this lane's launch after
        the copy), through host memory otherwise; in place where the lane
        may donate (:attr:`fused_donate`).  Upload coverage is left as it
        is: it records what came from the HOST, and who holds a strip's
        newest elements is ``Cores``'s to know.  Under this lane's phase
        lock.  Returns how it went: ``d2d`` or ``host``."""
        if src.device.platform == self.device.platform:
            moved, how = jax.device_put(strip, self.device), "d2d"
        else:
            moved, how = jax.device_put(np.asarray(strip), self.device), "host"
        put = _update_slice_donating if self.fused_donate else _update_slice
        self._buffers[id(arr)] = put(self._buffer_for(arr), moved,
                                     offset_elems)
        self._m_halo_exchanges.inc()
        self._m_halo_bytes.inc(strip.nbytes)
        return how

    def ensure_resident(self, arr: ClArray) -> Any:
        """Buffer for a non-read array: reuse cache or zeros (the kernel is
        expected to produce it)."""
        return self._buffer_for(arr)

    def buffer(self, arr: ClArray) -> Any:
        return self._buffers[id(arr)]

    def buffers_left(self, params: Sequence[ClArray]) -> tuple:
        """Weak references to this lane's buffers of ``params`` as they
        stand: what a fused window left when it closed (``Cores``).  Weak,
        so that a buffer an upload replaces later is not kept alive.
        Empty where the lane lacks one."""
        bufs = [self._buffers.get(id(p)) for p in params]
        if any(b is None for b in bufs):
            return ()
        return tuple(weakref.ref(b) for b in bufs)

    def still_holds(self, params: Sequence[ClArray], left: tuple) -> bool:
        """Whether this lane's buffers of ``params`` are the very arrays
        :meth:`buffers_left` saw: no upload and no other launch has
        replaced one since."""
        if len(left) != len(params):
            return False
        for p, ref in zip(params, left):
            buf = self._buffers.get(id(p))
            if buf is None or ref() is not buf:
                return False
        return True

    def set_buffer(self, arr: ClArray, buf: Any) -> None:
        self._buffers[id(arr)] = buf
        self._buffer_owner[id(arr)] = arr

    def invalidate(self, arr: ClArray) -> None:
        self._buffers.pop(id(arr), None)
        self._buffer_owner.pop(id(arr), None)
        self._uploaded.pop(id(arr), None)
        self._piece_counts.pop(id(arr), None)

    def reset_coverage(self) -> None:
        """Forget what has been uploaded WITHOUT dropping device buffers:
        the next enqueue-mode compute re-fetches its range from host.
        Called when a rebalance moves ranges — coverage records only ever
        grow, so a chip that lost a region and later re-acquires it would
        otherwise skip the re-upload and read stale data.  Bumps
        :attr:`coverage_epoch` so an in-flight fused window observes the
        reset and disengages (core/cores.py)."""
        self._uploaded.clear()
        self.coverage_epoch += 1

    # -- dispatch driver (fused path) ----------------------------------------
    def dispatch_preflight(self) -> None:
        """Fire this lane's submit-time failure checks (pending driver
        errors + the armed ``driver-submit`` fault point) without
        queuing — the fused batch dispatch runs this for EVERY lane
        before queuing ANY closure, so a refusal cannot leave lanes
        with diverged iteration counts (``_DriverQueue.preflight``)."""
        if self._driver is None:
            self._driver = _DriverQueue(
                self._m_driver_depth, name=f"fused:lane{self.index}",
                lane=self.index)
        self._driver.preflight()

    def dispatch_async(self, fn: Callable[[], None], depth: int = 2,
                       preflighted: bool = False) -> None:
        """Queue a dispatch closure on this chip's FIFO driver thread
        (created lazily).  ``depth`` bounds the in-flight backlog PER
        CALL — a runtime retune of the caller's knob applies to the next
        submit, not only to the queue's creation."""
        if self._driver is None:
            self._driver = _DriverQueue(
                self._m_driver_depth, name=f"fused:lane{self.index}",
                lane=self.index)
        self._driver.submit(fn, depth, preflighted=preflighted)

    def drain_dispatch(self) -> None:
        """Wait until every queued dispatch closure has run (host-side),
        re-raising the first failure.  No-op when the driver never
        started."""
        if self._driver is not None:
            self._driver.drain()

    # -- stream driver (streamed-transfer path) ------------------------------
    def stream_preflight(self) -> None:
        """Fire the stream driver's submit-time failure checks (armed
        ``driver-submit`` fault point + pending closure errors) without
        queuing — and WITHOUT creating the stream driver thread when
        streaming never engaged.  ``compute_fused_batch`` runs this for
        every lane before dispatching a per-call iteration, so an armed
        fault fires while nothing of the iteration has reached any lane
        (a CLEAN failure containment can re-dispatch)."""
        # ckcheck: ok GIL-visible read between iterations — the caller
        # is the single enqueue driver and no phase is in flight when
        # it preflights (compute() joined every worker phase)
        q = self._stream_driver
        if q is not None:
            q.preflight()
            return
        if FAULTS.enabled:
            try:
                FAULTS.raise_if_fired(
                    "driver-submit", lane=self.index,
                    where=f"stream:lane{self.index}")
            except Exception as e:  # noqa: BLE001 - marker, re-raised
                e._ck_clean_window = True
                raise

    def stream_dispatch_async(self, fn: Callable[[], None], depth: int = 2,
                              preflighted: bool = False) -> None:
        """Queue a streamed-transfer closure (commit + launch + D2H
        issue) on this chip's STREAM driver thread — separate from the
        fused driver on purpose: these closures run while the submitter
        holds the worker's phase lock, so they must never contend for
        worker locks (a fused closure queued ahead would deadlock the
        drain).  ``depth`` bounds how many chunks the caller thread may
        stage ahead of the dispatched chunk — the double buffer."""
        if self._stream_driver is None:
            self._stream_driver = _DriverQueue(
                self._m_stream_depth, name=f"stream:lane{self.index}",
                lane=self.index)
        self._stream_driver.submit(fn, depth, preflighted=preflighted)

    def drain_stream_dispatch(self) -> None:
        """Wait until every streamed-transfer closure has run (host-side
        dispatch; device completion is the fence's business), re-raising
        the first failure."""
        if self._stream_driver is not None:
            self._stream_driver.drain()

    # -- launch --------------------------------------------------------------
    def _mark_dispatch(self, tag: str, compute_id) -> None:
        """``part:call`` / ``part:handed`` (trace/spans.py): ONE pair a
        ``launch`` span, around its dispatches; between them is what the
        runtime takes to admit them.  Callers test their span's token."""
        TRACER.instant("engage", cid=compute_id, lane=self.index, tag=tag)

    def launch(
        self,
        program: KernelProgram,
        kernel_names: Sequence[str],
        params: Sequence[ClArray],
        value_args: Sequence,
        offset: int,
        size: int,
        local_range: int,
        global_size: int,
        step: int,
        repeats: int = 1,
        sync_kernel: str | None = None,
        compute_id: int | None = None,
        reach: str = "",
    ) -> None:
        """Run the kernel sequence over work items [offset, offset+size) on
        this chip.  ``repeats`` reruns the sequence on-device without host
        round-trips (reference: computeRepeated / repeatCount,
        Worker.cs:1051-1069); ``sync_kernel`` interleaves a synchronization
        kernel between repeats (computeRepeatedWithSyncKernel).
        ``compute_id`` tags the launch span and the per-cid completion
        probe used by the fence split — optional, purely observability;
        so is ``reach``, what the caller kept current beyond this range
        (``u1:16384``), which the launch and compile spans then carry.

        The launch ladder (:func:`launch_ladder`) has two lowerings and
        this is where a per-call launch picks one: a host loop over the
        rungs, one dispatch each, values passed at run time; or, when the
        ladder has MORE THAN ONE rung and a fused window (or
        ``Cores.warmup``) has already built the predicated-ladder
        executable of exactly this key, ONE dispatch of that executable
        with ``iters=1`` — the same rung functions in the same order,
        bit-identical (``KernelProgram.fused_launcher``).  The per-call
        path only peeks: it never builds that executable (values are baked
        into it, and per-call values may change every call)."""
        _tt = TRACER.t0("launch")
        bufs = tuple(self._buffers[id(p)] for p in params)
        names = list(kernel_names)
        units = size // step
        # a compute with a global offset runs items beyond the global
        # range: its launchers are built without the proofs drawn from it
        in_range = 0 <= offset and offset + size <= global_size
        dispatched = 0
        infos: list = []  # of the launchers run, for the span's lowering
        # device-timeline mark around the dispatch (trace/device.py):
        # disabled is one attribute read + falsy check, the tracer
        # discipline — the host-clock half of the launch's mark; its
        # sequence number rides the ``ck/launch`` annotation below, which
        # correlates this launch's device ops back to (cid, lane, kernel)
        _dm = MARKS.begin(names, compute_id, self.index) \
            if MARKS.enabled else None
        try:
            one_fn, one_args = None, ()
            if repeats > 1:
                # on-device repeat: the whole sequence × repeats is ONE
                # fused dispatch (lax.fori_loop inside jit) — no host
                # round-trips (reference: computeRepeated, Worker.cs:36-46)
                one_fn = program.sequence_launcher(
                    tuple(names), tuple(_ladder(size, step)), local_range,
                    global_size, repeats, sync_kernel, value_args,
                    platform=self.device.platform, in_range=in_range,
                )
                one_args = (offset, bufs)
            elif not sync_kernel and units & (units - 1):
                # more than one rung: ride the fused ladder executable IF
                # a window or warmup has built this very key (a peek)
                one_fn = program.fused_launcher(
                    tuple(names), step, global_size, local_range,
                    global_size, value_args, platform=self.device.platform,
                    donate=self.fused_donate, build=False, in_range=in_range,
                )
                one_args = self.ladder_scalars(offset, units, 1) + (bufs,)
            if one_fn is not None:
                one_fn.info.reach = reach
                if _tt:
                    self._mark_dispatch("part:call", compute_id)
                bufs = tuple(one_fn(*one_args))
                dispatched = 1
                infos.append(one_fn.info)
            else:
                # the host loop over the rungs (one rung, no executable
                # built for this key, unhashable values): interleave the
                # sync kernel between repeats like
                # computeRepeatedWithSyncKernel
                if repeats > 1 and sync_kernel:
                    seq: list[str] = []
                    for r in range(repeats):
                        seq.extend(names)
                        if r != repeats - 1:
                            seq.append(sync_kernel)
                    plan = [(seq, 1)]
                else:
                    plan = [(names, repeats)]
                # a rung keeps views only of what NO kernel of this launch
                # stores to: an array that comes back replaced call after
                # call would have its views built again every time
                frozen = program.frozen(
                    tuple(names) + ((sync_kernel,) if sync_kernel else ()))
                for names_seq, reps in plan:
                    for _ in range(reps):
                        for name in names_seq:
                            va = value_args.get(name, ()) if isinstance(value_args, dict) else tuple(value_args)
                            for chunk in _ladder(size, step):
                                fn, info = program.launcher(
                                    name, chunk, local_range, global_size,
                                    platform=self.device.platform,
                                    in_range=in_range,
                                )
                                n_arr = program.array_param_count(name)
                                info.reach = reach
                                if _tt and not dispatched:
                                    self._mark_dispatch("part:call",
                                                        compute_id)
                                out = fn(offset, bufs[:n_arr], tuple(va),
                                         frozen=frozen)
                                bufs = tuple(out) + bufs[n_arr:]
                                offset += chunk
                                dispatched += 1
                                if _tt:
                                    infos.append(info)
                            offset -= size  # rewind for next kernel/repeat
            if _tt:
                self._mark_dispatch("part:handed", compute_id)
        finally:
            if _dm is not None:  # close even on a failed dispatch
                MARKS.end(_dm)
        self._launched(params, bufs, compute_id, _tt, _dm,
                       _tt and f"{'+'.join(names)} x{dispatched}", dispatched,
                       infos)

    def _launched(self, params, bufs: tuple, compute_id, _tt, _dm,
                  tag: str, dispatched: int, infos) -> None:
        """The tail every launch shares once its dispatches are out: the
        buffer cache is REPLACED from the outputs (a donating executable
        has deleted the inputs), then the per-cid probe, the ``launch``
        span (with the lowering ``infos``' launchers were built with) and
        the markers."""
        for p, b in zip(params, bufs):
            self._buffers[id(p)] = b
        if not bufs:
            return
        if compute_id is not None and self.track_cid_outputs:
            # last output value of this cid's latest launch: the
            # fence-split completion probe (stream order means
            # materializing it waits for exactly this work).
            # Re-insert to refresh recency, bound to the 64 most
            # recent cids (the perf_log convention) — unbounded, a
            # fresh-cid-per-job caller would pin one stale device
            # buffer per cid forever
            self._cid_last_out.pop(compute_id, None)
            self._cid_last_out[compute_id] = bufs[0]
            if len(self._cid_last_out) > 64:
                self._cid_last_out.pop(next(iter(self._cid_last_out)))
        if _tt:
            TRACER.record(
                "launch", _tt, cid=compute_id, lane=self.index, tag=tag,
                **MARKS.meta(_dm), **lowering_meta(infos),
            )
        if self.markers is not None:
            # one marker per actual dispatch, added AFTER the dispatch
            # succeeded (a failed one must not leak an added-never-reached
            # marker into the in-flight accounting) and reached when the
            # final output retires on the chip (real in-flight depth, not
            # host-dispatch counting) — repeat mode, a fused ladder and a
            # per-call launch riding it all show O(1) dispatches
            self.markers.add(dispatched)
            self.markers.reach_when_ready(bufs[0], dispatched)

    def ladder_scalars(self, *values: int) -> tuple:
        """The ladder executable's run-time scalars (offset, units,
        iterations) as ``int32`` arrays kept on this lane.  A Python
        scalar handed to a dispatch is a host-to-device transfer of its
        own, every time; a window's dispatches repeat the same few values
        (the lane's offset and units, the ramp's iteration counts), so
        each is put on the device once and kept."""
        kept = self._ladder_scalars
        if len(kept) > 1024:  # a balancer that never settles: start over
            kept.clear()
        out = []
        for v in values:
            s = kept.get(v)
            if s is None:
                s = kept[v] = jax.device_put(np.int32(v), self.device)
            out.append(s)
        return tuple(out)

    @property
    def fused_donate(self) -> bool:
        """Whether this lane's fused ladder donates its buffer tuple: on a
        TPU (state stays HBM-resident across iterations without a
        transient double allocation), unless something still holds the
        PREVIOUS launch's outputs by reference — the per-cid completion
        probes (``fence_cid`` on a donated buffer reads a deleted array)
        or the marker thread (a deleted array "retires" its marker before
        the device did).  ``donate`` is part of the fused-launcher key:
        the warmup path reads this same property, so a warmed key equals
        the live one AS LONG AS neither switch is flipped afterwards —
        turning ``fine_grained_queue_control`` or ``fence_split`` on over a
        warmed ladder compiles the non-donating executable at the next
        window (chip_smoke stage 1 runs exactly that)."""
        return (self.device.platform == "tpu"
                and not self.track_cid_outputs
                and self.markers is None)

    def launch_fused(
        self,
        program: KernelProgram,
        kernel_names: Sequence[str],
        params: Sequence[ClArray],
        value_args: Sequence,
        offset: int,
        size: int,
        local_range: int,
        global_size: int,
        step: int,
        iters: int,
        compute_id: int | None = None,
        reach: str = "",
    ) -> None:
        """ONE dispatch running ``iters`` repetitions of the kernel
        sequence over this chip's range — the fused-iteration ladder
        (core/cores.py).  offset / units / iteration count are RUNTIME
        arguments of one cached executable
        (``KernelProgram.fused_launcher``), so the balancer re-splitting
        or the window size changing never recompiles.  Buffers are
        donated per :attr:`fused_donate`."""
        donate = self.fused_donate
        fn = program.fused_launcher(
            tuple(kernel_names), step, global_size, local_range,
            global_size, value_args, platform=self.device.platform,
            donate=donate,
            in_range=0 <= offset and offset + size <= global_size,
        )
        if fn is None:  # unhashable values — caller gates on this
            for _ in range(iters):
                self.launch(
                    program, kernel_names, params, value_args, offset,
                    size, local_range, global_size, step,
                    compute_id=compute_id, reach=reach,
                )
            return
        fn.info.reach = reach
        _tt = TRACER.t0("launch")
        bufs = tuple(self._buffers[id(p)] for p in params)
        # device-timeline mark (trace/device.py): the fused ladder is ONE
        # dispatch, so one mark covers all `iters` iterations; the
        # per-iteration fallback above marks inside launch() instead
        _dm = MARKS.begin(kernel_names, compute_id, self.index) \
            if MARKS.enabled else None
        try:
            if _tt:
                self._mark_dispatch("part:call", compute_id)
            bufs = tuple(fn(*self.ladder_scalars(offset, size // step, iters),
                            bufs))
            if _tt:
                self._mark_dispatch("part:handed", compute_id)
        finally:
            if _dm is not None:
                MARKS.end(_dm)
        self._launched(params, bufs, compute_id, _tt, _dm,
                       _tt and f"fused:{'+'.join(kernel_names)} x{iters}", 1,
                       (fn.info,))

    # -- readback ------------------------------------------------------------
    def download_async(
        self, arr: ClArray, offset_elems: int, size_elems: int, full: bool,
        kind: str = "download",
    ):
        """D2H: start an async copy of this chip's range (or the full array);
        returns a handle consumed by :meth:`finish_download`.  ``kind``
        names the span the finish records (``download-chunk`` for one
        ladder-aligned chunk of a streamed partition download)."""
        buf = self._buffers[id(arr)]
        if full:
            out = buf
            off = 0
            self._m_whole_down.inc(out.nbytes)
        else:
            out = _slice_out(buf, offset_elems, size_elems)
            off = offset_elems
        if self.markers is not None:
            self.markers.add()
        try:
            out.copy_to_host_async()
        except Exception:
            pass
        t_issued = time.perf_counter()
        # the read-back's first part mark (trace/spans.py): the copy is on
        # its way; ``finish_download`` marks ``part:landed``
        if TRACER.active():
            TRACER.instant(kind, lane=self.index, tag="part:issued",
                           bytes=out.nbytes, name=arr.name, off=off)
        return (arr, out, off, self.markers, self.index,
                self._m_download_bytes, kind, self._m_download_seconds,
                t_issued)

    def download_chunk_async(self, arr: ClArray, offset_elems: int, size_elems: int):
        """One ladder-aligned chunk of a STREAMED partition download:
        issued as soon as the chunk's last kernel launch is dispatched,
        so retired chunks drain D2H while later chunks still compute."""
        self._m_d2h_chunks.inc()
        return self.download_async(
            arr, offset_elems, size_elems, False, kind="download-chunk"
        )

    def download_slices_async(self, records) -> tuple[list, int]:
        """The flush's read-back of this lane, issued at once: ``records``
        are ``(arr, offset_elems, size_elems, full)``, ALL known before
        the first is issued (unlike the streamed per-call path, which
        issues a chunk when its kernel has been dispatched).  Every sliced
        record is cut into pieces of ONE length (:data:`PIECE_BYTES`, the
        buffer's own length where that is shorter) by ONE dispatch of
        :func:`_slice_pieces` for the whole lane; the pieces a record
        needs start their copy to the host, the rest (the count is a power
        of two that only grows) stay on the device.  A piece whose window
        would pass the buffer's end starts earlier, and every piece's
        handle ends with which of its elements are the record's (``skip``,
        ``take``): :meth:`finish_download` writes exactly ``[offset,
        offset + size)`` of each record.  A ``full`` record
        (``write_all``) is :meth:`download_async`'s.  Returns one list of
        handles a record (a handle's second element is the array that
        crosses) and the dispatches made.  The caller holds this lane's
        lock."""
        out: list[list] = [[] for _ in records]
        bufs, counts, lengths, starts, cuts = [], [], [], [], []
        for at, (arr, off, size, full) in enumerate(records):
            if full:
                out[at].append(self.download_async(arr, 0, arr.size, True))
            elif size > 0:
                buf = self._buffers[id(arr)]
                n = buf.shape[0]
                c = min(n, max(1, PIECE_BYTES // buf.dtype.itemsize))
                wanted = range(off, off + size, c)
                k = max(self._piece_counts.get(id(arr), 1),
                        1 << (len(wanted) - 1).bit_length())
                self._piece_counts[id(arr)] = k
                first = [min(want, n - c) for want in wanted]
                starts += first + first[-1:] * (k - len(first))
                cuts.append((at, arr, [
                    (want, want - start, min(c, off + size - want))
                    for want, start in zip(wanted, first)]))
                bufs.append(buf)
                counts.append(k)
                lengths.append(c)
        if bufs:
            pieces = _slice_pieces(tuple(bufs), np.asarray(starts, np.int32),
                                   tuple(counts), tuple(lengths))
            active = TRACER.active()
            for (at, arr, parts), got in zip(cuts, pieces):
                chunked = len(parts) > 1
                kind = "download-chunk" if chunked else "download"
                for piece, (want, skip, take) in zip(got, parts):
                    if chunked:
                        self._m_d2h_chunks.inc()
                    if self.markers is not None:
                        self.markers.add()
                    try:
                        piece.copy_to_host_async()
                    except Exception:
                        pass
                    t_issued = time.perf_counter()
                    if active:  # the record's own bytes, as ``landed`` says
                        TRACER.instant(
                            kind, lane=self.index, tag="part:issued",
                            bytes=take * piece.dtype.itemsize,
                            name=arr.name, off=want)
                    out[at].append((
                        arr, piece, want, self.markers, self.index,
                        self._m_download_bytes, kind,
                        self._m_download_seconds, t_issued, skip, take))
        return out, int(bool(bufs))

    @staticmethod
    def finish_download(handle) -> None:
        (arr, out, off, markers, lane, byte_counter, kind, seconds,
         t_issued, *cut) = handle
        _tt = TRACER.t0(kind)
        # capture the fault-plane state ONCE: a plane armed mid-call
        # would otherwise pair delay_s with the 0.0 sentinel t0 and
        # scale the injected sleep by absolute process uptime
        _faults = FAULTS.enabled
        _ft0 = time.perf_counter() if _faults else 0.0
        host = arr.host()
        data = np.asarray(out)
        if cut:  # a piece of the batched read-back: the record's elements
            data = data[cut[0] : cut[0] + cut[1]]
        # landed: the bytes are in host memory (jax's own); from here to
        # the span's end is the copy into the caller's array
        seconds.observe(time.perf_counter() - t_issued)
        if _tt:
            TRACER.instant(kind, lane=lane, tag="part:landed",
                           bytes=data.nbytes, name=arr.name, off=off)
        view = host[off : off + data.size]
        lib = _native_lib()
        if (
            lib is not None
            and data.nbytes >= (4 << 20)
            and view.size == data.size  # a truncated slice must go through
            # numpy assignment below so it RAISES like it always did,
            # never a GIL-free out-of-bounds write
            and view.flags["C_CONTIGUOUS"]
            and data.flags["C_CONTIGUOUS"]
            and view.dtype == data.dtype
        ):
            # multi-MB writeback: GIL-free parallel memcpy through the
            # native copy engine (kutuphane_tpu.cpp ck_copyParallel) —
            # concurrent worker joins stop serializing on the GIL
            lib.ck_copyParallel(
                view.ctypes.data, data.ctypes.data, data.nbytes, 4
            )
        else:
            view[:] = data
        byte_counter.inc(data.nbytes)
        if _faults:
            # chaos plane: the D2H half of an injected Nx slow link —
            # the flush drain's per-lane attribution (the balancer
            # floor's feed) sees the degradation like a real one
            d = FAULTS.delay_s("slow-link", lane=lane, where="d2h",
                               base_s=time.perf_counter() - _ft0)
            if d > 0.0:
                time.sleep(d)
        TRACER.record(kind, _tt, lane=lane, tag=arr.name, bytes=data.nbytes)
        if markers is not None:
            markers.reach()

    def fence(self) -> None:
        """Block until every dispatched op on this chip has retired,
        WITHOUT reading results back (the reference's finish() on the used
        queues, Worker.cs:364-423): ``block_until_ready`` over the cached
        buffers — no probe dispatch, no D2H."""
        # no span here: fence() is (almost) always driven by
        # Cores.barrier, whose own "fence" span covers the wait — a
        # second nested same-kind span would double-count fence time in
        # every per-kind total (the per-cid completion probes, fence_cid,
        # do record: they carry information the barrier span does not)
        # under the phase lock for the whole wait: a fused launch on a
        # TPU lane DONATES the cached buffers and swaps in its outputs
        # under this lock, so a snapshot waited on outside it could hold
        # an array another host thread's window has since deleted
        with self.lock:
            bufs = list(self._buffers.values())
            if not bufs:
                return
            t0 = time.perf_counter()
            jax.block_until_ready(bufs)
            # the device-to-host anchor: nothing this chip was handed
            # before this line can end after it
            TRACER.instant("fence", lane=self.index, tag="retired")
        self._m_fence_waits.inc()
        self._m_fence_seconds.observe(time.perf_counter() - t0)

    def fence_cid(self, compute_id: int) -> bool:
        """Block until this chip's work for ONE compute id has retired:
        wait on the cid's last launch output.  Stream
        order means this returns exactly when that cid's final kernel
        (and everything dispatched before it) completed — the per-cid
        completion wait behind the fence split (Cores.barrier with
        ``fence_split`` on).  Returns False when the cid never launched
        here (zero share)."""
        buf = self._cid_last_out.get(compute_id)
        if buf is None:
            return False
        _tt = TRACER.t0("fence")
        buf.block_until_ready()
        TRACER.record(
            "fence", _tt, cid=compute_id, lane=self.index, tag="cid-split"
        )
        return True

    def dispose(self) -> None:
        # driver first: a still-queued dispatch closure must finish (or
        # fail into the driver's error slot) before the buffers it reads
        # are cleared out from under it
        if self._driver is not None:
            self._driver.close()
            self._driver = None
        if self._stream_driver is not None:
            self._stream_driver.close()
            self._stream_driver = None
        self._buffers.clear()
        self._buffer_owner.clear()
        self._uploaded.clear()
        self.benchmarks.clear()
        self.transfer_benchmarks.clear()
        self._cid_last_out.clear()
        self._ladder_scalars.clear()
        self._piece_counts.clear()
        if self.markers is not None:
            self.markers.close()
            self.markers = None
