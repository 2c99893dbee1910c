"""Cores — the multi-chip scheduler: split / compute / join with iterative
load balancing.

TPU-native analogue of the reference's ``Cores`` (Cores.cs): owns one
:class:`Worker` per chip (Cores.cs:56,260-262), the per-compute-id
``global_ranges``/``global_references`` tables (Cores.cs:130-135), and the
``compute()`` orchestration entry (Cores.cs:471-963) — first call splits the
global range equally (Cores.cs:569-596), every later call re-partitions from
measured per-chip times via :func:`core.balance.load_balance`
(HelperFunctions.cs:190-280 port), then dispatches
H2D → launch → D2H per chip concurrently (the reference's
``Parallel.For`` phases, Cores.cs:746-835, become a thread pool over async
XLA dispatch).

Pipelined modes (reference: event pipeline Cores.cs:1236-1367 / driver
pipeline :1371-1858): the chip's range is cut into ``pipeline_blobs``
sub-ranges and blob k+1's H2D is issued while blob k computes — XLA async
dispatch plays the role of the 16 command queues; D2H copies start per blob
(``copy_to_host_async``) and are joined at the end.

Enqueue mode (reference: ClNumberCruncher.cs:125-129, Cores.cs:836-949):
skip host synchronization and readbacks entirely — data stays in HBM across
repeated computes until :meth:`flush` is called.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from contextlib import ExitStack, nullcontext
from dataclasses import dataclass, field, replace
from typing import Any, Sequence

from ..analysis import flag_row
from ..arrays.clarray import ClArray
from ..errors import (
    ComputeValidationError,
    FusedBatchError,
    InjectedFaultError,
    KernelVerifyError,
)
from ..hardware import Devices, rate_prior
from ..kernel.registry import KernelProgram, lowering_meta
from ..metrics.registry import REGISTRY
from ..obs.debugserver import DEBUG_PORT_ENV
from ..obs.decisions import DECISIONS
from ..obs.drain import DrainController, apply_quarantine
from ..obs.flight import FLIGHT, record_crash
from ..obs.health import HealthMonitor
from ..utils.faultinject import FAULTS
from ..trace.attribution import split_fence_benches
from ..trace.spans import TRACER
from .balance import (
    BalanceHistory,
    BalanceState,
    equal_split,
    load_balance,
    per_iteration_benches,
    prior_split,
)
from .compilecache import CACHE as COMPILE_CACHE
from .compilecache import trim_placed_jax_cache
from .stream import TransferTuner, chunk_plan
from .worker import Worker, launch_ladder

__all__ = ["Cores", "PIPELINE_EVENT", "PIPELINE_DRIVER", "ComputePerf",
           "job_signature"]


def job_signature(
    kernel_names, params, compute_id, global_range, local_range,
    global_offset, value_args,
) -> tuple:
    """Identity of one repeatable enqueue call — THE coalescing key.
    One function on purpose: the fused-window machinery
    (``Cores._fused_signature``) and the serving tier's request
    grouping (``serve.frontend.ServeJob.signature``) must build the
    identical tuple, else batches silently stop matching open windows
    and every dispatch rides the per-call fallback.  Params enter by
    OBJECT identity: the workers' buffer caches key on ``id(arr)``, so
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

PIPELINE_EVENT = 1   # reference: Cores.cs:416-423
PIPELINE_DRIVER = 2


@dataclass
class ComputePerf:
    """Per-compute-id performance record (reference: performanceReport,
    Cores.cs:994-1063)."""

    compute_id: int
    device_ms: list[float] = field(default_factory=list)
    device_items: list[int] = field(default_factory=list)
    total_ms: float = 0.0

    def report(self, device_names: list[str]) -> str:
        lines = [f"compute id {self.compute_id}: total {self.total_ms:.3f} ms"]
        tot = sum(self.device_items) or 1
        for name, ms, it in zip(device_names, self.device_ms, self.device_items):
            lines.append(
                f"  {name}: {ms:8.3f} ms  {it:>10} workitems  load {100.0 * it / tot:5.1f}%"
            )
        text = "\n".join(lines)
        return text


@dataclass
class _LanePlan:
    """What one lane does before its launch of a compute whose kernels read
    across lanes (``Cores._stage_exchange``)."""

    # position -> [(lo, hi)]: element intervals no lane holds; the lane's
    # phase uploads from the host what its coverage lacks of them
    host: dict = field(default_factory=dict)
    # uploads already staged from the host (``Worker.stage_upload``), and
    # strips already cut from the buffers of the lanes that wrote them last
    # (``(source lane, array, strip, offset)``: ``Worker.cut_strip``); the
    # phase lays both into its lane's buffers
    uploads: list = field(default_factory=list)
    strips: list = field(default_factory=list)
    # for the spans: what is kept current beyond the own range (``u1:128``)
    reach: str = ""


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
    # the eager sub-batch ramp: how many pending iterations the NEXT
    # flush of _fused_defer waits for — 1 when the window opens, doubled
    # by every such flush up to Cores.fused_batch
    ramp: int = 1
    # iterations this window has dispatched: a window that deferred at
    # least one has built (or found) its ladder executable on every lane
    dispatched: int = 0
    # what the window left when it closed: per row, weak references to
    # the lane's buffers of ``params`` after its last dispatch.  The next
    # window of this signature starts on the ladder only over these very
    # buffers (an upload or another compute's launch replaces them)
    left: list = field(default_factory=list)


def _own_split(owned: Sequence[tuple], lo: int, hi: int) -> list[tuple]:
    """``[lo, hi)`` cut along ``owned`` (sorted, disjoint ``(lo, hi,
    lane)`` intervals): the pieces ``(lo, hi, lane)`` in ascending order,
    ``lane`` None where no lane holds the elements."""
    out, at = [], lo
    for a, b, lane in owned:
        if b <= at:
            continue
        if a >= hi:
            break
        if a > at:
            out.append((at, a, None))
        at = min(b, hi)
        out.append((max(a, lo), at, lane))
    if at < hi:
        out.append((at, hi, None))
    return out


def _own_assign(owned: Sequence[tuple], lo: int, hi: int,
                lane: int) -> list[tuple]:
    """``owned`` with ``[lo, hi)`` given to ``lane``: whatever other
    intervals held of it is cut away, neighbours of one lane are joined."""
    cut = [(lo, hi, lane)]
    for a, b, who in owned:
        if a < min(b, lo):
            cut.append((a, min(b, lo), who))
        if max(a, hi) < b:
            cut.append((max(a, hi), b, who))
    out: list[tuple] = []
    for a, b, who in sorted(cut):
        if out and out[-1][2] == who and out[-1][1] == a:
            out[-1] = (out[-1][0], b, who)
        else:
            out.append((a, b, who))
    return out


def _strip_sizes(size: int, unit: int) -> list[int]:
    """A strip of ``size`` elements as pieces of few distinct sizes (each
    size is a compile of the slice that cuts it): the launch ladder over
    its whole ``unit``s, then the rest in one."""
    whole = size - size % unit
    return launch_ladder(whole, unit) + ([size - whole] if size > whole else [])


class Cores:
    """Scheduler over the selected chips."""

    def __init__(self, devices: Devices, program: KernelProgram):
        devices.require_nonempty("Cores device selection")
        self.devices = devices
        self.program = program
        # ladder manifest (core/compilecache.py): laid out at
        # construction, before the first engage records into it.  No-op
        # unless CK_COMPILE_CACHE is set.  jax's own executable cache is
        # configured once, at package import; a directory the package
        # placed is held under its size cap here.
        if COMPILE_CACHE.enabled:
            COMPILE_CACHE.arm()
        trim_placed_jax_cache()
        self.workers = [Worker(d.jax_device, i) for i, d in enumerate(devices)]
        # heterogeneous lanes (ISSUE 20): each lane's device KIND and
        # its table-derived relative-rate prior (hardware.rate_prior).
        # A mixed TPU + host-CPU fleet seeds its FIRST split from these
        # priors (prior_split in _ranges_for) instead of the equal
        # split, so the 10-100x-slower host lane starts near its
        # rate-implied share and the measured balancer only has to trim
        # — not rescue — the partition.  Both are plain attributes:
        # tools emulating a mixed fleet on virtual lanes (hetero_sweep,
        # resilience scenarios) overwrite rate_priors the same way they
        # pin fixed_compute_powers.  Homogeneous fleets see equal
        # priors, which _skewed_priors collapses to None — decision
        # logs and splits stay bit-identical to the pre-prior behavior.
        self.lane_kinds: list[str] = [
            str(getattr(d.jax_device, "device_kind",
                        d.jax_device.platform))
            for d in devices
        ]
        self.rate_priors: list[float] = [
            rate_prior(k) for k in self.lane_kinds]
        for i, kind in enumerate(self.lane_kinds):
            REGISTRY.gauge(
                "ck_lane_rate_prior",
                "table-derived relative-rate prior per lane",
                lane=i, ck_lane_kind=kind,
            ).set(self.rate_priors[i])
        self.pool = ThreadPoolExecutor(max_workers=max(1, len(self.workers)))
        # per-compute-id state (reference: Cores.cs:130-135)
        self.global_ranges: dict[int, list[int]] = {}
        self.global_references: dict[int, list[int]] = {}
        self.histories: dict[int, BalanceHistory] = {}
        self._balance_states: dict[int, BalanceState] = {}  # adaptive balancer state
        self._adaptive_load_balancer = True
        self._cont_ranges: dict[int, list[float]] = {}  # continuous state (parity mode)
        self.perf: dict[int, ComputePerf] = {}
        # rolling perf records per compute id (reference keeps only the
        # last report, Cores.cs:994-1063; we keep a queryable history)
        self.perf_log: dict[int, deque] = {}
        self.performance_feed = False
        self.smooth_load_balancer = True
        self.fixed_compute_powers: list[float] | None = None  # normalizedComputePowersOfDevices
        self.repeat_count = 1
        self.repeat_sync_kernel: str | None = None
        self.enqueue_mode = False
        self.no_compute_mode = False  # I/O only (reference: noComputeMode)
        # EVENT-engine read lookahead depth (blobs staged ahead of the
        # compute stage): 1 = the reference's 3-queue wavefront; deeper
        # keeps the inbound DMA lane busy when one blob's transfer
        # outlasts one compute step
        self.pipeline_lookahead = 2
        # deferred-readback records: (seq, worker, array, offset, size,
        # write_all, compute_id) — cid rides along so the flush drain
        # can attribute each lane's D2H wall back to the balancer
        self._enqueued: list[tuple] = []
        # per-cid iteration count since the last FLUSH (not the last
        # window — _enqueue_iters resets per barrier): the drain's
        # divisor, so the transfer feed lands in the same per-ITERATION
        # milliseconds the enqueue benches use (a per-flush total vs a
        # per-iteration bench would over-floor every lane ~window-size-x)
        self._flush_iters: dict[int, int] = {}
        self._lock = threading.Lock()
        self.last_compute_id: int | None = None
        # enqueue-mode rebalance state: compute ids dispatched since the
        # last barrier (+ the dispatch-window start time) and the ids whose
        # benches the barrier refreshed — those MAY rebalance on their next
        # call even in enqueue mode (the reference pins enqueue-mode work to
        # one device, Cores.cs:836-949; we rebalance at sync points instead,
        # the moral equivalent of feeding event benches into loadBalance,
        # HelperFunctions.cs:190-280)
        self._enqueue_cids: set[int] = set()
        self._enqueue_t0: float | None = None
        self._enqueue_rebalance: set[int] = set()
        # per-window iteration counts per compute id: the balancer's
        # window-granularity feedback normalizes fence-retire times to
        # per-iteration benches (balance.per_iteration_benches) so windows
        # of different sizes feed a consistent scale
        self._enqueue_iters: dict[int, int] = {}
        # monotone sequence tag on deferred readback records — flush()
        # orders host writes chronologically by it (list indices stopped
        # being chronological once per-worker flushes could interleave)
        self._enqueue_seq = 0
        # ---- fused-iteration dispatch (the enqueue dispatch-floor
        # collapse): when an enqueue window repeats the same compute id
        # with unchanged ranges and HBM-resident operands, its calls are
        # DEFERRED (a counter increment) and dispatched in batches as ONE
        # dynamic-iteration-count ladder executable per device
        # (Worker.launch_fused / KernelProgram.fused_launcher), through a
        # depth-limited per-device driver queue so device B's ladder
        # dispatch overlaps device A's execution.  A process's first
        # window finds out per call that it repeats (call 1 seeds the
        # candidate, call 2 engages, calls 3.. defer); a window that
        # repeats the LAST one, over the buffers that one left, starts on
        # the ladder at once: its first call defers like the others
        # (_fused_start names why not, fused_stats["window_starts"]).
        # Rebalance decisions stay at window boundaries (barrier), fed
        # per-iteration marginal times.  The eager sub-batch RAMPS: the
        # first deferral of a window is dispatched alone, then 2, 4, 8, ..
        # (_FusedRun.ramp), so the device starts on the window's first
        # iteration and each dispatch goes out while the one before it
        # runs; fused_batch is the ramp's cap, the most iterations one
        # eager dispatch carries; fused_queue_depth bounds the per-device
        # host dispatch backlog.
        self.fused_dispatch = True
        self.fused_batch = 16
        self.fused_queue_depth = 2
        # window identity/state: ALL writes hold self._lock; compute()'s
        # fast path reads them lock-free (one attribute read per enqueue
        # call) and _fused_defer revalidates under the lock before
        # counting — the stale-read window is the design, the lock'd
        # revalidation is the correctness
        # ckcheck: ok racy fast-path read, revalidated in _fused_defer
        self._fused_sig: tuple | None = None
        # ckcheck: ok racy fast-path read, revalidated in _fused_defer
        self._fused_run: _FusedRun | None = None
        # last per-call enqueue signature: a window engages only on a
        # CONSECUTIVE repeat, so a window that never repeats (mixed cids
        # ping-ponging A,B,A,B) pays one tuple compare per call instead
        # of an engage/break(close+drain) cycle per call
        self._fused_candidate: tuple | None = None
        # the last window that closed after deferring at least one
        # iteration (its ladder executables exist, ``left`` names the
        # buffers it left): what _fused_start opens the next window from.
        # Written by _fused_close / _fused_start under the lock.
        self._fused_last: _FusedRun | None = None
        # True while compute_fused_batch runs a per-call iteration it
        # already lane-preflighted: stream-driver submits inside the
        # iteration skip their own fault fire (a mid-phase fire would
        # be a dirty cross-lane failure containment cannot repair).
        # Single-writer by the enqueue single-driver contract.
        self._batch_preflighted = False
        self._fused_pending = 0
        # serializes [grab pending + submit to drivers] so a close/drain
        # cannot slip between a concurrent flush's grab and its submits
        # (downloads would then precede the in-flight ladder and the host
        # would miss those iterations)
        self._fused_mu = threading.Lock()
        # observability: windows dispatched, iterations fused, and every
        # disengage with its named reason — a perf regression to the
        # per-iteration path must be attributable, never silent.  The
        # dict stays as the per-cruncher API (tests and /statusz read
        # it); the metrics registry carries the same counts process-wide
        # (ck_fused_* series) for the uniform Prometheus export.
        # Writes hold the scheduler lock / fused mutex; READERS (delta
        # snapshots, /statusz) are reporting-only and tolerate a
        # mid-window value by design — the counters only ever grow.
        # ckcheck: ok reporting-only reads; monotone counters, snapshot semantics
        self.fused_stats: dict[str, Any] = {
            "windows": 0, "fused_iters": 0, "deferred_iters": 0,
            "disengaged": {},
            # how each enqueue window's first compute went: "ladder"
            # (deferred as the window's first iteration) or the named
            # reason it took the per-call path (_fused_start)
            "window_starts": {},
        }
        # cached metric handles for the fused warm path (one dispatch
        # per batch; the deferral itself counts into fused_stats alone —
        # it IS the dispatch-floor collapse, "a counter increment"); the
        # per-reason disengage counter stays get-or-create — disengages
        # are cold
        self._m_fused_windows = REGISTRY.counter(
            "ck_fused_windows_total", "fused ladder dispatch batches")
        self._m_fused_iters = REGISTRY.counter(
            "ck_fused_iters_total",
            "iterations dispatched via fused ladders")
        self._m_barriers = REGISTRY.counter(
            "ck_barriers_total", "enqueue-window sync points")
        # ---- streamed partition transfers (the read/compute/write
        # pipeline WITHIN one lane's partition): the plain path's
        # monolithic upload → ladder → download becomes a chunked
        # wavefront — the caller thread stages chunk j+1's H2D while the
        # per-worker stream driver (depth stream_queue_depth — the
        # double buffer) dispatches chunk j's commit + ladder launch,
        # and retired chunks' D2H issues while later chunks compute
        # (_run_streamed).  Chunks are step·2^k (chunk_plan), so every
        # chunk launch is a compile-once ladder cache hit.
        # stream_chunks: 0 = autotune (transfer_tuner), n = pin.
        self.streamed_transfers = True
        self.stream_chunks = 0
        self.stream_queue_depth = 2
        self.transfer_tuner = TransferTuner()
        # cached handle — no registry get-or-create on the compute path
        # (the PR 4 fused-counter discipline)
        self._m_stream_retunes = REGISTRY.counter(
            "ck_stream_retune_total",
            "transfer-autotuner re-tunes forced by re-partitions")
        # observability: per-lane chunk count of the last streamed phase
        # (the autotuner's live choice; also exported as the
        # ck_stream_chunk_count gauge).  Written on the phase thread
        # under the worker lock; readers (workloads reporting, /statusz)
        # take no lock by design — a one-phase-stale chunk count is
        # reporting, not a decision input.
        # ckcheck: ok reporting-only reads; one-slot-per-lane, stale tolerated
        self.last_stream_chunks: dict[int, int] = {}
        # kernel-verify advisory dedupe, keyed on (kernel sequence,
        # first finding fingerprint) — NOT object identity: the
        # program's verdict cache is written lock-free, so a racing
        # first-verify can hand this method a verdict the cache then
        # drops, and a recycled id() would suppress a different
        # shape's one-and-only advisory forever
        self._verify_notified: set[tuple] = set()
        # ---- who holds the current elements (reads across lanes): per
        # array, the lane whose buffer holds the newest value of each
        # element interval, from the ranges of every compute and the
        # arrays its kernels store to: id(array) -> (array, sorted
        # disjoint (lo, hi, lane)).  Kept from the first compute whose
        # kernels read beyond their own range (the analysis' proved
        # reach: _stage_exchange); before a lane's launch of such a
        # compute the parts of its reach that another lane wrote last are
        # fetched from THAT lane's buffer, device to device, and only
        # what no lane wrote comes from the host.  Reads and writes hold
        # the scheduler lock; a lane's upload coverage stays under its
        # worker lock as before.
        self._owners: dict[int, tuple] = {}
        # compute ids whose computes exchanged in the open window: said
        # once why they do not fuse, and left out of the barrier's feed to
        # the balancer (their lanes retire in lock-step: see barrier)
        self._exchanging: set[int] = set()
        # per-cid fence splitting (VERDICT r5 #8): when on, barrier()
        # fences each compute id's last output in last-dispatch order and
        # feeds the balancer MARGINAL per-cid times instead of charging
        # the whole-window fence time to every id dispatched in a mixed
        # window (trace/attribution.split_fence_benches).  Off by
        # default: the split costs one extra completion wait per cid in the
        # window (plus workers pinning the probe buffers), and
        # homogeneous windows (one kernel per window) are measured
        # exactly either way.
        self._fence_split = False
        self._enqueue_cid_order: list[int] = []
        # host-gated dispatch (reference: ClUserEvent bound to queues +
        # Worker.cs:487-557 synchronized start): when set, every worker
        # lane blocks on the event before its compute phase, so triggering
        # starts all lanes simultaneously
        self.dispatch_gate = None
        # lane tracing (observability for the multi-chip dispatch proof):
        # when on, each plain-path lane records (worker index, dispatch-done
        # timestamp, join-done timestamp) — dispatch-done is when the async
        # XLA launch returned to the host, join-done is when the lane's
        # readbacks materialized.  All lanes dispatching before the first
        # join completes is the "N chips in flight concurrently" evidence.
        self.trace_lanes = False
        self.lane_trace: dict[int, list[tuple[int, float, float]]] = {}
        # lane health scoring (obs/health.py): rolling per-lane baselines
        # over fence walls, transfer walls, and stream-driver stalls,
        # fed at sync points / phase tails (never the deferral hot path);
        # health_report() / /healthz read the verdicts, suggest_drain()
        # is advisory only (eviction is ROADMAP item 4's business)
        self.health = HealthMonitor()
        # drain ACTUATOR (obs/drain.py): consumes the monitor's
        # verdicts at every barrier — a degraded lane is quarantined
        # (share masked to 0 via apply_quarantine in _ranges_for, the
        # displaced share redistributed onto surviving lanes), probed
        # after a hold, and re-admitted with hysteresis when the
        # verdict clears.  Advisory became action (ROADMAP item 4).
        self.drain = DrainController(self.health, lanes=len(self.workers))
        # live introspection plane (obs/debugserver.py): started by
        # serve_debug() or, for the FIRST Cores in the process, by
        # CK_DEBUG_PORT (a busy port is skipped silently — one debug
        # plane per process, whoever binds first owns it)
        self._debug_server = None
        env_port = os.environ.get(DEBUG_PORT_ENV)
        if env_port:
            try:
                port = int(env_port)
                # a FIXED port only: port 0 binds a fresh ephemeral
                # server per Cores (bind never fails), so the busy-port
                # guard that enforces one-plane-per-process never fires
                # and scrapers have no stable address — use
                # serve_debug(0) explicitly for ephemeral ports
                if port <= 0:
                    raise ValueError("CK_DEBUG_PORT must be a fixed port > 0")
                self.serve_debug(port)
            except (OSError, ValueError) as e:
                FLIGHT.event("debug-port-skipped", port=env_port,
                             reason=f"{type(e).__name__}: {e}")

    @property
    def adaptive_load_balancer(self) -> bool:
        """Adaptive per-chip damping (:class:`BalanceState`) — the default.
        Setting ``False`` restores the reference's fixed 0.3 damping + flat
        history window (HelperFunctions.cs:246) exactly; toggling either way
        clears the per-compute-id balancer state so the two modes never feed
        each other stale continuous ranges or mis-weighted history rows."""
        return self._adaptive_load_balancer

    @adaptive_load_balancer.setter
    def adaptive_load_balancer(self, v: bool) -> None:
        v = bool(v)
        if v != self._adaptive_load_balancer:
            self._adaptive_load_balancer = v
            self.histories.clear()
            self._balance_states.clear()
            self._cont_ranges.clear()

    @property
    def fence_split(self) -> bool:
        return self._fence_split

    @fence_split.setter
    def fence_split(self, v: bool) -> None:
        v = bool(v)
        self._fence_split = v
        for w in self.workers:
            # workers record per-cid completion-probe buffers only while
            # the split can consume them — each record pins a device
            # buffer, a cost computes with the flag off must not pay;
            # turning OFF also releases the already-pinned probes (with
            # the flag off nothing can ever read them again)
            w.track_cid_outputs = v
            if not v:
                with w.lock:
                    w._cid_last_out.clear()

    @property
    def num_devices(self) -> int:
        return len(self.workers)

    def device_names(self) -> list[str]:
        return [d.name for d in self.devices]

    # -- range tables --------------------------------------------------------
    def _skewed_priors(self) -> list[float] | None:
        """The lane rate priors, or ``None`` when they carry no signal
        (homogeneous fleet / stale length after a device-set edit).
        ``None`` keeps every homogeneous split and decision record
        bit-identical to the pre-prior behavior — the prior path only
        engages when the fleet actually mixes device kinds."""
        pr = self.rate_priors
        if (pr and len(pr) == self.num_devices
                and len(set(float(p) for p in pr)) > 1):
            return [float(p) for p in pr]
        return None

    def _ranges_for(
        self, compute_id: int, total: int, step: int, rebalance: bool
    ) -> tuple[list[int], list[int]]:
        n = self.num_devices
        ranges = self.global_ranges.get(compute_id)
        if ranges is None or sum(ranges) != total or len(ranges) != n:
            if self.fixed_compute_powers is not None:
                # user-pinned static shares (reference:
                # normalizedComputePowersOfDevices, ClNumberCruncher.cs:254-271)
                shares = self.fixed_compute_powers
                raw = [total * s for s in shares]
                ranges = [max(0, int(r / step + 0.5)) * step for r in raw]
                diff = total - sum(ranges)
                while diff != 0:
                    i = max(range(n), key=lambda k: shares[k])
                    ranges[i] += step if diff > 0 else -step
                    diff = total - sum(ranges)
            else:
                priors = self._skewed_priors()
                if priors is not None and n > 1:
                    # prior-seeded first split (ISSUE 20): land near the
                    # rate-implied share immediately; the measured
                    # balancer refines from there
                    ranges = prior_split(total, step, priors,
                                         cid=compute_id)
                else:
                    ranges = equal_split(total, n, step)
        elif rebalance and n > 1 and self.fixed_compute_powers is None:
            # ckcheck: ok racy bench read — staleness tolerated by the
            # balancer (decay/refresh converge it); writers hold w.lock
            bench = [w.benchmarks.get(compute_id, 0.0) for w in self.workers]
            if all(b > 0 for b in bench):
                hist = None
                if self.smooth_load_balancer:
                    hist = self.histories.setdefault(
                        compute_id,
                        BalanceHistory(weighted=self.adaptive_load_balancer),
                    )
                # transfer-aware: each lane's separately-measured H2D+D2H
                # time floors its effective cost — a lane whose link
                # cannot feed it must not be assigned shares its compute
                # bench alone would justify (unequal effective link
                # bandwidth, the reference's multi-GPU PCIe reality)
                transfer = [
                    # ckcheck: ok racy bench read — same contract as above
                    w.transfer_benchmarks.get(compute_id, 0.0)
                    for w in self.workers
                ]
                if not any(t > 0.0 for t in transfer):
                    transfer = None
                if self.adaptive_load_balancer:
                    state = self._balance_states.setdefault(compute_id, BalanceState())
                    ranges = load_balance(
                        bench, ranges, total, step, hist, state=state,
                        transfer_ms=transfer, jump_start=True,
                        cid=compute_id,
                        rate_prior=self._skewed_priors(),
                    )
                else:
                    carry = self._cont_ranges.setdefault(compute_id, [])
                    ranges = load_balance(bench, ranges, total, step, hist,
                                          carry=carry, cid=compute_id,
                                          rate_prior=self._skewed_priors())
        # drain mask (obs/drain.py): quarantined lanes hold 0, probation
        # lanes hold exactly one probe step, displaced share moves to
        # the actives — applied to CACHED tables too (idempotent), so a
        # barrier-time drain takes effect on the very next call even
        # without an armed rebalance
        if self.drain.enabled:
            drained = self.drain.drained_lanes()
            probing = self.drain.probe_lanes()
            if drained or probing:
                ranges = apply_quarantine(ranges, step, drained, probing)
        self.global_ranges[compute_id] = ranges
        refs = [0] * n
        acc = 0
        for i in range(n):
            refs[i] = acc
            acc += ranges[i]
        self.global_references[compute_id] = refs
        return ranges, refs

    # -- main entry (reference: Cores.compute, Cores.cs:471-963) -------------
    def compute(
        self,
        kernel_names: Sequence[str],
        params: Sequence[ClArray],
        compute_id: int,
        global_range: int,
        local_range: int,
        global_offset: int = 0,
        pipeline: bool = False,
        pipeline_blobs: int = 4,
        pipeline_type: int = PIPELINE_EVENT,
        cruncher=None,
        value_args: Sequence | dict = (),
    ) -> None:
        for name in kernel_names:
            if name not in self.program:
                raise ComputeValidationError(
                    f"kernel {name!r} not in program; available: {self.program.kernel_names}"
                )
            need_vals = self.program.value_param_names(name)
            given = (
                len(value_args.get(name, ()))
                if isinstance(value_args, dict)
                else len(tuple(value_args))
            )
            if need_vals and given != len(need_vals):
                raise ComputeValidationError(
                    f"kernel {name!r} takes {len(need_vals)} scalar value argument(s) "
                    f"{need_vals} but {given} given — pass values=(...) to compute()"
                )
        step = local_range * (pipeline_blobs if pipeline else 1)
        if global_range % step != 0:
            raise ComputeValidationError(
                f"global_range ({global_range}) must be divisible by step ({step})"
            )
        t_start = time.perf_counter()
        # the call's "enqueue" span (ring and, in a profiler session, the
        # ck/enqueue annotation): closed by _fused_defer for a deferred
        # call and before the engage walk for a per-call one.  The first
        # call of an enqueue window, and every non-windowed call, opens
        # the next ``win``: the identifier its spans share across threads
        _tt = TRACER.t0("enqueue")
        # ckcheck: ok racy read — a window id is an observation aid
        if _tt and (not self.enqueue_mode or self._enqueue_t0 is None):
            TRACER.next_window()
        # Enqueue mode cannot rebalance on per-call host benches (they only
        # measure async dispatch time), so ranges hold still BETWEEN syncs
        # and move AT them: barrier() times each chip's retirement fence and
        # feeds that into the balancer, arming a one-shot rebalance for the
        # next call (the reference supports enqueue mode single-device only,
        # Cores.cs:836-949; its multi-device path rebalances per call on
        # event benches — ours does at sync granularity).  Residency stays
        # correct across a move because workers skip re-uploads only for
        # covered ranges (Worker.upload_covers).
        #
        # Fused-iteration fast path: with an active fused window whose
        # signature this call matches, the call is a counter increment —
        # the accumulated iterations dispatch in batches as ONE ladder
        # executable per device (see _fused_try_engage).  Every break-out
        # names its reason (fused_stats + a "fused" trace instant) so a
        # regression to per-iteration dispatch is attributable.
        # this call opens an enqueue window (the first since a barrier):
        # ``how`` it went per call (the reason) is counted and rides its span
        # ckcheck: ok racy read — a window start is an observation aid
        opens = self.enqueue_mode and self._enqueue_t0 is None
        how = None
        if self.enqueue_mode and self._fused_sig is not None and not pipeline:
            sig = self._fused_signature(
                kernel_names, params, compute_id, global_range,
                local_range, global_offset, value_args,
            )
            if self._sig_equal(sig, self._fused_sig):
                run = self._fused_run
                # the runtime mode toggles are NOT part of the signature
                # (they are cruncher state, not call identity) — re-check
                # them per deferral, else flipping one mid-window would
                # silently defer a call whose semantics changed (e.g.
                # repeat_count=3 deferring as ONE iteration)
                if self._fused_modes_off():
                    # clear the candidate so this call's tail records ONE
                    # event ("mode-change"), not a second engage-refusal
                    # under another name for the same call.  Under the
                    # lock: the candidate is written by concurrent host
                    # threads' engage tails (ckcheck lockset finding —
                    # an unlocked clear could resurrect a candidate
                    # another thread just replaced)
                    with self._lock:
                        self._fused_candidate = None
                    self._fused_break("mode-change")
                # ckcheck: ok one-shot arm: a stale read only delays the
                # rebalance by one call; arm/disarm writes hold _lock
                elif compute_id in self._enqueue_rebalance:
                    # a barrier armed a rebalance: ranges may move — the
                    # window's pinned per-device rows are no longer valid
                    self._fused_break("range-change")
                elif run is not None and any(
                    w.coverage_epoch != ep for w, ep in run.epochs
                ):
                    # a sync-point rebalance (possibly another thread's)
                    # reset upload coverage mid-window: operands are no
                    # longer guaranteed HBM-resident for these rows
                    self._fused_break("non-resident")
                elif self._fused_defer(t_start, kernel_names, _tt):
                    return
            else:
                self._fused_break("signature-change")
        elif self._fused_sig is not None and pipeline:
            self._fused_break("pipeline")
        elif self._fused_sig is not None and not self.enqueue_mode:
            # leaving enqueue mode without flush() (callers normally go
            # through the cruncher setter, which flushes)
            self._fused_break("enqueue-off")
        elif self.enqueue_mode:
            # no fused window is open.  One that repeats the last window,
            # over the buffers that window left, starts on the ladder: this
            # call is its first deferred iteration, and nothing of the
            # per-call path below runs (_fused_start says why not)
            how = "mode" if pipeline else self._fused_start(
                self._fused_signature(
                    kernel_names, params, compute_id, global_range,
                    local_range, global_offset, value_args,
                ), compute_id, global_offset)
            if how is None:
                if self._fused_defer(t_start, kernel_names, _tt,
                                     "ladder" if opens else ""):
                    if opens:
                        self._note_window_start("ladder")
                    return
                how = "closed"  # by another thread, before the deferral
        # kernel partition-safety / flag-soundness gate (analysis/,
        # docs/STATIC_ANALYSIS.md "Kernel partition-safety"): verdicts
        # cache per launch shape in the program, so steady state pays
        # one env read + one dict hit.  Deferred fused calls never
        # reach this point — the window's engage call already verified
        # the identical shape.  Advisory by default (one flight event
        # per shape); CK_KERNEL_VERIFY=strict raises the named finding.
        verify_mode = os.environ.get("CK_KERNEL_VERIFY", "advisory")
        # a read that leaves its item's window with a proved reach is no
        # error where this path keeps the reach current (the plain path:
        # not a pipelined compute, not an on-device repeat, which has no
        # host between its passes); ``exchange`` is the verdict of such a
        # compute on more than one lane: what _stage_exchange goes by
        exchange = None
        if verify_mode != "off":
            verdict = self.program.verify(
                tuple(kernel_names),
                tuple(flag_row(p.flags) for p in params),
                window=self.enqueue_mode or self.repeat_count > 1,
                exchange=not pipeline and self.repeat_count == 1
                and not self.repeat_sync_kernel,
                lanes=self.num_devices,
            )
            if verdict.errors:
                if verify_mode == "strict":
                    raise KernelVerifyError(verdict.errors[0])
                self._note_kernel_verdict(verdict, kernel_names)
            elif verdict.reach and self.num_devices > 1:
                reach = self._reach_elements(verdict, kernel_names, value_args)
                # a synchronous compute whose reads are all full and that
                # stores to nothing it reads across lanes takes the whole
                # arrays from the host, as ever
                if reach and (self.enqueue_mode or any(
                        params[pos].flags.partial_read
                        or pos in verdict.writes for pos in reach)):
                    exchange = (verdict, reach)
        if opens and how is not None:
            if exchange is not None:
                how = "halo"
            self._note_window_start(how)
        if self.enqueue_mode:
            # under the lock: concurrent host threads may drive different
            # compute ids through one Cores, and the order list's
            # remove+append is not atomic like the set add is
            with self._lock:
                self._note_enqueue_call(compute_id, t_start)
        old_ranges = list(self.global_ranges.get(compute_id, ()))
        _ts = TRACER.t0("schedule")
        ranges, refs = self._ranges_for(
            compute_id,
            global_range,
            step,
            rebalance=(not self.enqueue_mode)
            # ckcheck: ok one-shot arm — same contract as the check above
            or compute_id in self._enqueue_rebalance,
        )
        TRACER.record("schedule", _ts, cid=compute_id)
        with self._lock:
            # same lock as barrier's |= : a discard interleaved into the
            # set union would un-arm a rebalance the barrier just armed
            self._enqueue_rebalance.discard(compute_id)
        if ranges != old_ranges:
            TRACER.instant(
                "split" if not old_ranges else "rebalance",
                cid=compute_id, tag=str(ranges),
            )
            FLIGHT.event(
                "rebalance", cid=compute_id, ranges=list(ranges),
                old=list(old_ranges),
            )
            # balancer health (metrics registry): per-cid per-device share
            # gauges set on CHANGE only (steady state costs nothing); the
            # re-split itself is the flight event and the instant above
            for i, r in enumerate(ranges):
                REGISTRY.gauge(
                    "ck_balance_share", "per-chip work-item share",
                    cid=compute_id, lane=i,
                ).set(r)
            if old_ranges and (
                len(old_ranges) != len(ranges)
                or any(abs(a - b) > step
                       for a, b in zip(ranges, old_ranges))
            ):
                # a MATERIAL re-partition moved the bytes: the transfer
                # autotuner's observations describe partitions that no
                # longer exist — drop them (the duplex-probe link seed
                # survives) so the next streamed phase re-tunes its
                # chunk count.  ±1-quantization-step flaps are absorbed
                # instead: bytes_bucket's power-of-two hysteresis exists
                # for exactly those wiggles, and wiping on every flap
                # would park every key in a perpetual measuring run
                self.transfer_tuner.on_repartition()
                self._m_stream_retunes.inc()
        if self.enqueue_mode and old_ranges and ranges != old_ranges \
                and exchange is None:
            # (a compute that reads across lanes fetches a gained strip
            # from the lane that held it, as it fetches its reach: below)
            # the balancer moved shares between syncs: host arrays must be
            # made current BEFORE any chip uploads its newly-acquired region
            # (the freshest data for that region is on the previous owner's
            # HBM; its deferred download record is in the pending list) —
            # and every chip's upload-coverage record is reset, else a chip
            # RE-acquiring a range it held before an earlier move would
            # pass upload_covers() on stale coverage and skip the fetch of
            # data another chip updated in between.  The flush and the
            # reset are ONE atomic step under every worker's lock
            # (_flush_and_reset_coverage): interleaved with another host
            # thread's in-flight enqueue window, a non-atomic
            # flush-then-reset let that thread launch between the two and
            # then re-upload a host copy missing its own increments — the
            # r7 KNOWN LIMIT's lost updates, now closed by the
            # window-scoped coverage epoch (each reset bumps
            # Worker.coverage_epoch; fused windows check it per deferral,
            # per-call windows re-upload from a host made current inside
            # the same atomic step).
            _tr = TRACER.t0("resync")
            self._flush_and_reset_coverage()
            TRACER.record("resync", _tr, cid=compute_id, tag="range-move")
        # a chip whose share was quantized to zero never re-runs its bench;
        # decay its stale measurement so a one-off slow call (e.g. first-call
        # compile) cannot starve it permanently.  The transfer floor decays
        # with it — a zero-range lane moves no bytes either, so a transient
        # link hiccup would otherwise pin max(bench, transfer) at the stale
        # link cost forever no matter how far the compute bench decays.
        # Under the worker lock: the `*=` read-modify-write races a driver
        # thread's end_bench / a concurrent flush's transfer feed — an
        # interleaved store loses one side's update (ckcheck lockset
        # finding, PR 7; the bench dicts' writers all hold w.lock now)
        for i, w in enumerate(self.workers):
            if ranges[i] > 0:
                continue
            with w.lock:
                if w.benchmarks.get(compute_id, 0.0) > 0.0:
                    w.benchmarks[compute_id] *= 0.5
                if w.transfer_benchmarks.get(compute_id, 0.0) > 0.0:
                    w.transfer_benchmarks[compute_id] *= 0.5

        # write_all owner: "device i writes array (i mod numDevices)"
        # (Worker.cs:871-885) — but only among chips that actually run,
        # else a starved owner would silently skip the readback
        active = [i for i in range(self.num_devices) if ranges[i] > 0]
        write_all_owner = {
            idx: active[idx % len(active)]
            for idx, p in enumerate(params)
            if p.flags.write_all and active
        }

        if self.trace_lanes:
            # the trace describes ONE call: stale entries from earlier calls
            # would mix into the first-join comparison and leak memory
            with self._lock:
                self.lane_trace.pop(compute_id, None)
        # part marks of the per-call path (trace/spans.py): where the
        # caller cuts the strips, hands out the lanes' phases, waits for
        # them, and notes what they wrote
        plans = {}
        if exchange is not None:
            TRACER.instant("engage", cid=compute_id, tag="part:stage")
            plans = self._stage_exchange(
                exchange, params, compute_id, global_offset, ranges, refs,
                step,
            )
        TRACER.instant("engage", cid=compute_id, tag="part:submit")
        futures = []
        for i, w in enumerate(self.workers):
            if ranges[i] <= 0:
                continue
            futures.append(
                self.pool.submit(
                    TRACER.bind(self._run_worker, w.index),
                    w,
                    kernel_names,
                    params,
                    compute_id,
                    global_offset + refs[i],
                    ranges[i],
                    local_range,
                    global_range,
                    pipeline,
                    pipeline_blobs,
                    pipeline_type,
                    value_args,
                    write_all_owner,
                    plans.get(i),
                )
            )
        TRACER.instant("engage", cid=compute_id, tag="part:join")
        errs = []
        for f in futures:
            try:
                f.result()
            except Exception as e:  # surface the first worker error
                errs.append(e)
        TRACER.instant("engage", cid=compute_id, tag="part:note")
        if errs:
            # black box before the raise: a crashed compute leaves the
            # flight ring + span ring + metrics on disk when
            # CK_POSTMORTEM_DIR is armed (obs/flight.py)
            record_crash("cores.compute", errs[0], lanes=self._lane_config())
            raise errs[0]

        # the lanes' launches are out: what they stored to, they now hold
        # ckcheck: ok racy emptiness peek — a compute's own arrays enter
        # the map on its own thread; the writes below hold the lock
        if exchange is not None or self._owners:
            self._note_writers(
                exchange, params, global_offset, ranges, refs)
        if _tt:
            TRACER.record(
                "enqueue", _tt, cid=compute_id, tag="+".join(kernel_names),
                **({"start": "per-call:" + how} if opens and how else {}),
            )
        self._record_perf(compute_id, t_start, ranges)
        if exchange is not None and self.enqueue_mode:
            # no fusion across an exchange: the computes of such a window
            # cannot be deferred into one device-side loop a lane, each is
            # its own launch with the fetch before it.  Said once a window.
            with self._lock:
                said = compute_id in self._exchanging
                self._exchanging.add(compute_id)
            if self.fused_dispatch and not said:
                self._note_disengage("halo", compute_id)
            return
        # fused-window engagement: a successfully dispatched enqueue call
        # whose next identical call would be a pure launch (operands
        # resident, ranges pinned) establishes the window this call's
        # geometry defines — subsequent matching calls defer
        if self.enqueue_mode and self.fused_dispatch and not pipeline:
            _te = TRACER.t0("engage")
            self._fused_try_engage(
                kernel_names, params, compute_id, global_range,
                local_range, global_offset, value_args, ranges, refs, step,
            )
            TRACER.record("engage", _te, cid=compute_id)

    def _note_kernel_verdict(self, verdict, kernel_names) -> None:
        """Advisory-mode surfacing of an unsafe launch shape: one
        flight event per distinct (kernel sequence, finding) — a
        value key, stable across racing verdict constructions."""
        f = verdict.errors[0]
        key = (tuple(kernel_names), f.fingerprint)
        with self._lock:
            if key in self._verify_notified:
                return
            self._verify_notified.add(key)
        FLIGHT.event(
            "kernel-verify", kernels="+".join(kernel_names),
            finding=f.kind, kernel=f.kernel, param=f.param, line=f.line,
            errors=len(verdict.errors),
        )

    # -- reads across lanes: who holds the current elements -----------------
    def _reach_elements(self, verdict, kernel_names, value_args) -> dict:
        """``{position: (below, above)}``: the elements beyond a lane's
        own range that this launch's kernels read, from the verdict's
        proved reach and the compute's values."""
        def values_of(kernel: str) -> dict:
            vals = (value_args.get(kernel, ()) if isinstance(value_args, dict)
                    else tuple(value_args))
            return dict(zip(self.program.value_param_names(kernel), vals))

        try:
            return verdict.reach_elements(values_of)
        except ValueError as e:
            raise ComputeValidationError(str(e)) from None

    def _stage_exchange(
        self, exchange, params, compute_id: int, global_offset: int,
        ranges, refs, step: int,
    ) -> dict:
        """Before the lanes of a compute that reads across lanes launch:
        ``[offset - reach, offset + size + reach)`` of every array its
        kernels read must be current on each lane.  Returns one
        :class:`_LanePlan` a lane.

        Inside an enqueue window the intervals that ANOTHER lane wrote
        last come from that lane's buffer, which its launch of the compute
        before left, and only what no lane holds comes from the host.  All
        strips are CUT here (``Worker.cut_strip``: a slice on the writer's
        device, nothing waits), on the caller's thread, before any lane's
        phase of this compute is submitted: a phase replaces its lane's
        buffers, and a neighbour must read what the compute before left.
        Each lane's phase then brings its strips over and lays them in
        (``Worker.lay_strip``), the lanes side by side.  A range that moved
        is the same fetch: the gained strip's last writer is the lane that
        held it.

        A synchronous compute takes everything from the host (which the
        compute before made current), widened by the reach where
        ``partial_read`` sends the slice alone; every lane's host reads
        are staged here and joined before any phase starts, because a
        phase ends by writing its lane's results into the same host
        arrays: a lane that uploaded late read its neighbour's rows of
        the NEXT step."""
        verdict, reach = exchange
        windowed = self.enqueue_mode
        with self._lock:
            owned = {pos: self._owners.get(id(params[pos]), (None, ()))[1]
                     for pos in verdict.reads} if windowed else {}
        tag = ";".join(f"{params[pos].name}:{max(r)}"
                       for pos, r in sorted(reach.items()))
        plans: dict = {}
        staging: list = []
        for i, w in enumerate(self.workers):
            if ranges[i] <= 0:
                continue
            plan = plans[i] = _LanePlan(reach=tag)
            off = global_offset + refs[i]
            fetch: list = []
            for pos in verdict.reads:
                p = params[pos]
                fl = p.flags
                epw = fl.elements_per_work_item
                below, above = reach.get(pos, (0, 0))
                lo = max(0, off * epw - below)
                hi = min(p.size, (off + ranges[i]) * epw + above)
                if fl.read and not fl.write_only:
                    whole = (lo, hi) if fl.partial_read else (0, p.size)
                    plan.host[pos] = [
                        (a, b) for a, b, lane in _own_split(
                            owned.get(pos, ()), *whole) if lane is None]
                fetch += [
                    (p, a, b, lane, step * epw) for a, b, lane in _own_split(
                        owned.get(pos, ()), lo, hi)
                    if lane is not None and lane != i]
            if not windowed:
                def stage(w=w, plan=plan):
                    plan.uploads = [
                        w.stage_upload(params[pos], a, b - a, settled=True)
                        for pos, pieces in plan.host.items()
                        for a, b in pieces]

                staging.append(self.pool.submit(TRACER.bind(stage, i)))
            for p, a, b, lane, unit in fetch:
                src = self.workers[lane]
                for n in _strip_sizes(b - a, unit):
                    with src.lock:
                        plan.strips.append((src, p, src.cut_strip(p, a, n), a))
                    a += n
        for f in staging:
            f.result()
        return plans

    def _note_writers(self, exchange, params, global_offset: int,
                      ranges, refs) -> None:
        """After a compute's launches are out: each lane holds the newest
        elements of its own range of every array the kernels store to
        (the verdict's word for a compute that reads across lanes; for any
        other compute, every array in the map that is not ``read_only``)."""
        with self._lock:
            if exchange is not None:
                stored = [params[pos] for pos in exchange[0].writes]
            else:
                stored = [p for p in params if id(p) in self._owners
                          and not p.flags.read_only]
            for p in stored:
                epw = p.flags.elements_per_work_item
                owned = self._owners.get(id(p), (p, ()))[1]
                for i, size in enumerate(ranges):
                    if size > 0:
                        lo = (global_offset + refs[i]) * epw
                        owned = _own_assign(
                            owned, lo, min(p.size, lo + size * epw), i)
                self._owners[id(p)] = (p, owned)

    def _record_perf(
        self, compute_id: int, t_start: float, ranges: list[int]
    ) -> None:
        perf = ComputePerf(
            compute_id=compute_id,
            # ckcheck: ok racy bench read — reporting only
            device_ms=[w.benchmarks.get(compute_id, 0.0) for w in self.workers],
            device_items=list(ranges),
            total_ms=(time.perf_counter() - t_start) * 1000.0,
        )
        self.perf[compute_id] = perf
        self.perf_log.setdefault(compute_id, deque(maxlen=64)).append(perf)
        self.last_compute_id = compute_id
        if self.performance_feed:
            print(perf.report(self.device_names()))

    # -- fused-iteration dispatch (the enqueue dispatch-floor collapse) ------
    @staticmethod
    def _sig_equal(a: tuple | None, b: tuple | None) -> bool:
        """Signature equality that treats ANY comparison failure as a
        mismatch: array-valued value args make tuple ``==`` raise
        (ambiguous elementwise truth) — such a call must take the
        signature-change path, never crash mid-window."""
        if a is None or b is None:
            return False
        try:
            return bool(a == b)
        except Exception:  # noqa: BLE001 - mismatch by definition
            return False

    def _note_enqueue_call(self, compute_id: int, t_start: float) -> None:
        """Window bookkeeping shared by the per-call and deferred paths
        (one code path on purpose: the cid order feeds the fence split,
        the iteration counts feed the balancer's per-iteration
        normalization).  Caller holds the scheduler lock."""
        if self._enqueue_t0 is None:
            self._enqueue_t0 = t_start
        if compute_id in self._enqueue_cids:
            # keep the order list in LAST-dispatch order — the fence
            # split probes completions ascending, and a cid's last
            # launch is what its probe waits on
            self._enqueue_cid_order.remove(compute_id)
        self._enqueue_cid_order.append(compute_id)
        self._enqueue_cids.add(compute_id)
        self._enqueue_iters[compute_id] = (
            self._enqueue_iters.get(compute_id, 0) + 1
        )
        self._flush_iters[compute_id] = (
            self._flush_iters.get(compute_id, 0) + 1
        )

    def _fused_signature(
        self, kernel_names, params, compute_id, global_range,
        local_range, global_offset, value_args,
    ) -> tuple:
        """Identity of one repeatable enqueue call — delegates to the
        shared :func:`job_signature` (the serving tier builds the same
        tuple to group requests; one construction keeps them from
        drifting apart)."""
        return job_signature(
            kernel_names, params, compute_id, global_range, local_range,
            global_offset, value_args,
        )

    def _fused_try_engage(
        self, kernel_names, params, compute_id, global_range,
        local_range, global_offset, value_args, ranges, refs, step,
    ) -> None:
        """Open a fused window for this call's signature, or record WHY
        not (fused_stats["disengaged"] + a "fused" trace instant) — every
        refusal reason is observable so a silent fall-back to
        per-iteration dispatch cannot masquerade as device slowness.

        Engagement requires a CONSECUTIVE repeat of the signature: the
        first sighting only seeds the candidate, so a window that never
        repeats (mixed cids alternating every call) costs one tuple
        compare per call — no engage walk, no break/drain cycle, and no
        misleading disengage stats for calls that were never going to
        fuse."""
        sig = self._fused_signature(
            kernel_names, params, compute_id, global_range,
            local_range, global_offset, value_args,
        )
        # swap under the scheduler lock: with concurrent host threads the
        # unlocked read-modify-write could interleave with another
        # thread's swap and engage a window off a candidate that thread
        # already replaced (ckcheck lockset finding, PR 7)
        with self._lock:
            candidate, self._fused_candidate = self._fused_candidate, sig
        if not self._sig_equal(sig, candidate):
            return
        reason = None
        if self.no_compute_mode:
            reason = "no-compute"
        elif self.repeat_count > 1 or self.repeat_sync_kernel:
            # each call already fuses its repeats on device
            # (sequence_launcher); cross-call fusion would change the
            # sync-kernel interleaving contract
            reason = "repeat-mode"
        elif self.dispatch_gate is not None:
            reason = "dispatch-gate"
        elif self.trace_lanes:
            reason = "trace-lanes"
        if reason is None:
            try:
                hash(sig)
            except TypeError:
                reason = "unhashable-values"
        rows: list = []
        if reason is None:
            rows = self._rows_of(ranges, refs, global_offset)
            if not self._rows_covered(rows, params):
                # this call needed a partial upload the window would have
                # to repeat — the deferral contract (pure launch) fails
                reason = "partial-upload"
        if reason is not None:
            self._note_disengage(reason, compute_id)
            return
        run = _FusedRun(
            sig=sig, compute_id=compute_id,
            kernel_names=tuple(kernel_names), params=tuple(params),
            value_args=value_args, local_range=local_range,
            global_range=global_range, step=step, rows=rows,
            # ckcheck: ok monotone epoch int — one GIL-atomic read
            epochs=[(w, w.coverage_epoch) for w, _off, _size in rows],
        )
        with self._lock:
            self._fused_sig = sig
            self._fused_run = run
        self._fused_engaged(run)

    def _rows_of(self, ranges, refs, global_offset: int) -> list:
        """A fused window's rows, ``(worker, global offset, range size)``
        for every lane with a share, from a compute id's range table."""
        return [(w, global_offset + refs[i], ranges[i])
                for i, w in enumerate(self.workers) if ranges[i] > 0]

    def _rows_covered(self, rows, params) -> bool:
        """Whether every array the kernels read is resident on every
        row's lane over the range a launch there reads (the enqueue-mode
        residency test of the per-call path, ``Worker.upload_covers``):
        the deferral contract is a pure launch."""
        single = self.num_devices == 1
        for w, off, size in rows:
            for p in params:
                fl = p.flags
                if fl.read and not fl.write_only:
                    epw = fl.elements_per_work_item
                    full = single or not fl.partial_read
                    if not w.upload_covers(
                        p,
                        0 if full else off * epw,
                        p.size if full else size * epw,
                    ):
                        return False
        return True

    def _fused_engaged(self, run: _FusedRun) -> None:
        """What every opened fused window records, however it opened."""
        compute_id, rows = run.compute_id, run.rows
        FLIGHT.event("fused-engage", cid=compute_id, rows=len(rows))
        # persistent-cache seam (core/compilecache.py): an engaged
        # window's spec is what a joining process would need to warm —
        # persist it here (engagement is cold: once per window open,
        # never the defer path; the cache's seen-set bounds the probe
        # to one per distinct key per process)
        if COMPILE_CACHE.enabled:
            self._cache_record_engaged(run)
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

    def _fused_modes_off(self) -> bool:
        """A runtime mode toggle that no fused window may run under (they
        are cruncher state, not part of a call's signature, so every
        deferral and every window start re-checks them)."""
        return bool(
            not self.fused_dispatch
            or self.no_compute_mode
            or self.repeat_count > 1
            or self.repeat_sync_kernel
            or self.dispatch_gate is not None
            or self.trace_lanes
        )

    def _fused_start(self, sig: tuple, compute_id: int,
                     global_offset: int) -> str | None:
        """A compute in enqueue mode found no fused window open: open one
        ON THE LADDER if this call repeats the last window, so that it is
        deferred as the window's first iteration and the per-call path
        (verify, range table, the pool hop, a per-call launch that hands
        its values over at run time) never runs.  Returns ``None`` when
        the window is open, else the named reason the call goes per call
        (``fused_stats["window_starts"]``), the first that holds of:

        - ``mode``: a runtime toggle no fused window runs under;
        - ``first-sighting`` / ``values-changed``: the signature is not
          the last per-call or fused one (the candidate, which survives a
          barrier); only its values differ, or more;
        - ``range-change``: a barrier (or a drain transition) armed a
          rebalance of this compute id, a lane is drained or on probation,
          or the range table no longer reads what the last window ran;
        - ``halo``: some compute of this scheduler reads across lanes
          (who holds which elements is tracked per compute: no deferral);
        - ``never-fused``: the last window of this signature deferred
          nothing, so no ladder executable of this key was ever built.
          This path only PEEKS (``fused_launcher(build=False)``): a
          window of one compute never compiles a ladder for it;
        - ``non-resident``: upload coverage was reset since, or a lane's
          buffers are no longer the ones that window left (an upload, a
          launch of another compute);
        - ``partial-upload``: an array the kernels read is not covered;
        - ``closed``: another host thread opened or closed a window between
          this call's checks and its deferral.

        What the per-call first compute leaves behind for later is left
        here too: the deferred-readback records (``flush()`` and a range
        move read them); ``_fused_defer`` does the window bookkeeping."""
        if self._fused_modes_off():
            return "mode"
        # ckcheck: ok racy read — the open below revalidates under the lock
        candidate = self._fused_candidate
        if not self._sig_equal(sig, candidate):
            same_but_values = (candidate is not None
                               and candidate[:-1] == sig[:-1])
            return "values-changed" if same_but_values else "first-sighting"
        # ckcheck: ok one-shot arm: same contract as compute()'s reads
        if compute_id in self._enqueue_rebalance or (
                self.drain.enabled and (self.drain.drained_lanes()
                                        or self.drain.probe_lanes())):
            return "range-change"
        # ckcheck: ok racy emptiness peek, as compute()'s
        if self._owners:
            return "halo"
        # ckcheck: ok racy read — a closed run is never written again, and
        # the open below revalidates under the lock
        last = self._fused_last
        if last is None or not self._sig_equal(last.sig, sig):
            return "never-fused"
        ranges = self.global_ranges.get(compute_id)
        refs = self.global_references.get(compute_id)
        if (ranges is None or refs is None
                or self._rows_of(ranges, refs, global_offset) != last.rows):
            return "range-change"
        for w, off, size in last.rows:
            if self.program.fused_launcher(
                    last.kernel_names, last.step, last.global_range,
                    last.local_range, last.global_range, last.value_args,
                    platform=w.device.platform, donate=w.fused_donate,
                    build=False,
                    in_range=0 <= off and off + size <= last.global_range,
            ) is None:
                return "never-fused"
        for (w, epoch), left in zip(last.epochs, last.left):
            # ckcheck: ok monotone epoch int — one GIL-atomic read
            if w.coverage_epoch != epoch or not w.still_holds(
                    last.params, left):
                return "non-resident"
        if not self._rows_covered(last.rows, last.params):
            return "partial-upload"
        run = replace(last, ramp=1, dispatched=0, left=[])
        active = [w.index for w, _off, _size in run.rows]
        with self._lock:
            if self._fused_sig is not None:
                return "closed"  # another thread opened a window meanwhile
            if any(w.coverage_epoch != epoch for w, epoch in run.epochs):
                return "non-resident"
            for idx, p in enumerate(run.params):
                fl = p.flags
                if not (fl.write and not fl.read_only):
                    continue
                for w, off, size in run.rows:
                    # write_all: the owning lane alone defers a readback
                    if not fl.write_all or \
                            w.index == active[idx % len(active)]:
                        self._defer_readback(w, p, off, size, compute_id)
            self._fused_sig = sig
            self._fused_run = run
        self._fused_engaged(run)
        return None

    def _defer_readback(self, w: Worker, p: ClArray, offset: int,
                        size: int, compute_id: int) -> None:
        """One deferred-readback record (enqueue mode): ``flush()`` and a
        range move read back the newest a lane and array.  Caller holds
        the scheduler lock."""
        self._enqueue_seq += 1
        self._enqueued.append(
            (self._enqueue_seq, w, p, offset, size, p.flags.write_all,
             compute_id))

    def _note_window_start(self, how: str) -> None:
        """How an enqueue window's first compute went: ``ladder`` or the
        reason it took the per-call path; the dict and the registry carry
        the same counts."""
        with self._lock:
            d = self.fused_stats["window_starts"]
            d[how] = d.get(how, 0) + 1
        REGISTRY.counter(
            "ck_fused_window_start_total",
            "enqueue windows by how their first compute went", how=how,
        ).inc()

    def _fused_defer(self, t_start: float, kernel_names, span=0.0,
                     start: str = "") -> bool:
        """Count this call into the active fused window.  Returns False
        when the window was concurrently closed (caller falls through to
        the per-call path).  ``span`` is the caller's open "enqueue" span
        (falsy while the tracer is inactive); ``start`` rides it where
        this call opened its enqueue window on the ladder.

        The eager sub-batch ramps: the pending iterations are dispatched
        once they number ``run.ramp``, which starts at 1 when a window
        opens and doubles with every such dispatch up to ``fused_batch``
        (1, 2, 4, 8, 16, 16, ...): the device starts on the window's first
        deferred iteration, and each dispatch goes out while the one
        before it runs.  The same in every window: a count, not a probe."""
        with self._lock:
            run = self._fused_run
            if run is None or self._fused_sig is None:
                return False
            cid = run.compute_id
            self._note_enqueue_call(cid, t_start)
            self._fused_pending += 1
            cap = max(1, int(self.fused_batch))
            due = self._fused_pending >= min(run.ramp, cap)
            if due:
                run.ramp = min(2 * run.ramp, cap)
            self.fused_stats["deferred_iters"] += 1
        if due:
            self._fused_flush()
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
        if self.performance_feed:
            # the feed wants a printed row per call — keep the full
            # record on that (diagnostic) configuration only
            self._record_perf(cid, t_start, self.global_ranges.get(cid, []))
        else:
            # deferral budget is "a counter increment" (r7 attribution:
            # scheduler_dispatch residue) — building a ComputePerf here
            # per deferred call costs three list allocations + a deque
            # append for a row whose device numbers are stale anyway
            # (the window hasn't dispatched).  One real row lands per
            # window in _dispatch_fused.
            self.last_compute_id = cid
        return True

    def _dispatch_fused(self, run: _FusedRun, iters: int) -> None:
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
            for w, _off, _size in run.rows:
                w.dispatch_preflight()
        except Exception:
            # the worker preflight stamps _ck_clean_window per raise
            # source: True for the injected fault (fired before any
            # closure queued), False for a popped pending error (an
            # EARLIER closure's work never applied — re-dispatch could
            # silently corrupt)
            with self._lock:
                self._fused_sig = None
                self._fused_run = None
                self._fused_candidate = None
            raise
        try:
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

                w.dispatch_async(dispatch, depth=self.fused_queue_depth,
                                 preflighted=True)
        except Exception:
            # a submit failure (a driver re-raising an error a closure
            # hit since the preflight) after some rows were queued
            # leaves devices with DIVERGED iteration counts for this
            # batch — poison the window so a caller that catches the
            # error cannot keep deferring into it (the next call goes
            # per-call; the cruncher's error gate additionally refuses
            # further work until reset)
            with self._lock:
                self._fused_sig = None
                self._fused_run = None
                self._fused_candidate = None
            raise
        with self._lock:
            run.dispatched += iters
            self.fused_stats["windows"] += 1
            self.fused_stats["fused_iters"] += iters
        self._m_fused_windows.inc()
        self._m_fused_iters.inc(iters)
        # one ComputePerf per dispatched window (total_ms = this
        # dispatch pass) — the per-window row the per-deferral fast
        # path above stopped paying for
        self._record_perf(run.compute_id, _t_pass,
                          self.global_ranges.get(run.compute_id, []))
        FLIGHT.event("fused-window", cid=run.compute_id, iters=iters)
        if _tt:
            # the lowering of the rungs in the lanes' fused executables: a
            # peek, so a process's FIRST window, whose executables the
            # closures above are still to trace, has none to name (its
            # lanes' ``launch`` spans do)
            fns = [self.program.fused_launcher(
                tuple(run.kernel_names), run.step, run.global_range,
                run.local_range, run.global_range, run.value_args,
                platform=w.device.platform, donate=w.fused_donate,
                build=False,
                in_range=0 <= off and off + size <= run.global_range)
                for w, off, size in run.rows]
            infos = [fn.info for fn in fns if fn is not None and fn.info.rungs]
            TRACER.record(
                "fused", _tt, cid=run.compute_id, tag=f"x{iters}",
                **(lowering_meta(infos) if infos else {}))

    # ckcheck: cold window boundary — runs once a sub-batch of the ramp
    def _fused_flush(self) -> None:
        """Dispatch the accumulated deferred iterations (window stays
        open).  Under _fused_mu so a concurrent close cannot drain the
        drivers between our pending-grab and our submits."""
        with self._fused_mu:
            with self._lock:
                run, k = self._fused_run, self._fused_pending
                self._fused_pending = 0
            if run is not None and k > 0:
                self._dispatch_fused(run, k)

    def _fused_close(self) -> None:
        """End the fused window at a sync point: stop deferrals, dispatch
        the residue, and drain the per-device drivers (host-side dispatch
        complete — device completion is the caller's fence).  A window that
        deferred anything is kept as ``_fused_last``: the next window
        re-engages through its first per-call iteration, or, where it
        repeats this one, starts on the ladder (``_fused_start``)."""
        with self._fused_mu:
            with self._lock:
                run, k = self._fused_run, self._fused_pending
                self._fused_pending = 0
                self._fused_sig = None
                self._fused_run = None
                if run is not None:
                    self._fused_last = None
            if run is not None and k > 0:
                self._dispatch_fused(run, k)
        _td = TRACER.t0("drain")
        try:
            self._fused_drain()
        finally:
            TRACER.record("drain", _td)
        if run is not None and run.dispatched:
            # the drivers have drained: the lanes hold what the window's
            # last dispatch left.  The next window of this signature may
            # start on the ladder over these buffers (_fused_start)
            run.left = [w.buffers_left(run.params) for w, _o, _s in run.rows]
            with self._lock:
                self._fused_last = run

    def _note_disengage(self, reason: str, cid: int | None) -> None:
        """The one disengage-accounting path: fused_stats dict bump,
        flight event, decision row and "fused" trace instant."""
        with self._lock:
            d = self.fused_stats["disengaged"]
            d[reason] = d.get(reason, 0) + 1
        FLIGHT.event("fused-disengage", reason=reason, cid=cid)
        if DECISIONS.enabled:
            DECISIONS.record(
                "fused-disengage", {"cid": cid}, {"reason": reason})
        TRACER.instant("fused", cid=cid, tag=f"disengage:{reason}")

    def _fused_break(self, reason: str) -> None:
        """_fused_close plus the disengage bookkeeping: the named reason
        lands in fused_stats and as a "fused" trace instant."""
        with self._lock:
            run = self._fused_run
        cid = run.compute_id if run is not None else None
        self._fused_close()
        self._note_disengage(reason, cid)

    # -- externally-assembled batches (the serving tier's entry) -------------
    def _batch_defer(self, sig: tuple, k: int, t_start: float) -> bool:
        """Count ``k`` iterations into the open fused window matching
        ``sig`` in ONE step — the externally-assembled batch's deferral
        (``compute_fused_batch``) — then flush, so the whole batch
        lands as ONE ladder dispatch per device.  Returns False when no
        healthy matching window is open (the caller falls back to the
        per-call path); the guard re-checks exactly what the per-call
        deferral re-checks: runtime mode toggles, an armed rebalance,
        and the coverage epoch (a mid-batch reset means operands are no
        longer guaranteed HBM-resident)."""
        with self._lock:
            run = self._fused_run
            if (
                run is None
                or not self._sig_equal(self._fused_sig, sig)
                or self._fused_modes_off()
                or run.compute_id in self._enqueue_rebalance
                or any(w.coverage_epoch != ep for w, ep in run.epochs)
            ):
                return False
            cid = run.compute_id
            # ONE order-list touch + bulk iteration-count bumps: k
            # repeated _note_enqueue_call calls would pay k redundant
            # remove/append cycles on the cid order list while holding
            # the scheduler lock against every concurrent deferral
            self._note_enqueue_call(cid, t_start)
            if k > 1:
                self._enqueue_iters[cid] += k - 1
                self._flush_iters[cid] += k - 1
            self._fused_pending += k
            self.fused_stats["deferred_iters"] += k
        self._fused_flush()
        return True

    def compute_fused_batch(
        self,
        kernel_names: Sequence[str],
        params: Sequence[ClArray],
        compute_id: int,
        global_range: int,
        local_range: int,
        iters: int,
        global_offset: int = 0,
        value_args: Sequence | dict = (),
    ) -> dict:
        """Dispatch an EXTERNALLY-ASSEMBLED batch of ``iters`` identical
        enqueue iterations — the serving tier's coalesced-dispatch entry
        (``serve/frontend.py``): a front-end that already holds K
        same-signature requests must not pay K per-call dispatches to
        get them fused.

        The first iteration(s) ride the per-call :meth:`compute` path
        (uploads, range table, window bookkeeping, organic fused-window
        engagement — at most two calls when the signature is fusable,
        one when the window's candidate already matches from a previous
        batch); once a matching window is open, the REMAINDER counts in
        as one batch deferral and flushes immediately: ONE
        dynamic-iteration-count ladder dispatch per device for the whole
        residue, bit-identical to ``iters`` per-call computes (the
        per-call fallback below preserves that equivalence when fusion
        cannot apply — mode toggles, non-resident operands, unhashable
        values — so callers never need their own fallback).

        Requires :attr:`enqueue_mode` (the batch contract is deferred
        readbacks; results land at the caller's ``barrier``/``flush``).
        Returns ``{"iters", "fused", "ladder_iters", "per_call_iters"}``
        — observability for the coalesce-ratio accounting (the ladder
        iterations also count into ``fused_stats`` / ``ck_fused_*``
        like any fused window).

        A dispatch failure raises :class:`~..errors.FusedBatchError`
        carrying the NAMED cause, how many iterations applied before the
        failure, and whether the failed residue is ``clean``
        (preflight-refused before any lane's closure was queued — see
        ``_dispatch_fused`` — so re-dispatching it is bit-exact).  The
        serving tier's blast-radius containment
        (``serve/resilience.py``) is the consumer."""
        iters = int(iters)
        if iters < 1:
            raise ComputeValidationError(
                f"compute_fused_batch needs iters >= 1, got {iters}")
        if not self.enqueue_mode:
            raise ComputeValidationError(
                "compute_fused_batch requires enqueue_mode (deferred "
                "readbacks are the batch contract)")
        sig = self._fused_signature(
            kernel_names, params, compute_id, global_range, local_range,
            global_offset, value_args,
        )
        # fused-batch phase hook (obs/reqtrace.py): sample the
        # persistent compile cache's probe counters around the batch so
        # the serving tier can stamp a `warm-compile` lifecycle phase
        # when THIS window paid a miss.  One attribute read when the
        # cache is unarmed.
        probe_cache = COMPILE_CACHE.enabled
        if probe_cache:
            from .compilecache import probe_counts

            hits0, misses0 = probe_counts()
        done = 0
        ladder = 0
        try:
            while done < iters:
                t_start = time.perf_counter()
                # ckcheck: ok racy reads — single enqueue driver
                opens = self._enqueue_t0 is None
                deferred = self._batch_defer(sig, iters - done, t_start)
                if not deferred and self._fused_sig is None:
                    # no window open: one that repeats the last starts
                    # on the ladder, the whole batch in it
                    deferred = (
                        self._fused_start(sig, compute_id, global_offset)
                        is None
                        and self._batch_defer(sig, iters - done, t_start))
                    if deferred and opens:
                        self._note_window_start("ladder")
                if deferred:
                    ladder = iters - done
                    done = iters
                    break
                # lane preflight BEFORE the per-call dispatch: an armed
                # driver-submit clause (fused or stream queue) fires
                # here, while nothing of this iteration has reached any
                # lane — a CLEAN failure containment can re-dispatch.
                # The iteration's own stream submits then skip their
                # fire (_batch_preflighted): a mid-phase fire after
                # some lanes launched would be dirty by construction.
                if FAULTS.enabled:
                    # the worker preflight stamps _ck_clean_window per
                    # raise source (fault = clean, popped prior error
                    # = NOT clean — see _DriverQueue.preflight)
                    for w in self.workers:
                        w.stream_preflight()
                self._batch_preflighted = True
                try:
                    self.compute(
                        kernel_names, params, compute_id, global_range,
                        local_range, global_offset=global_offset,
                        value_args=value_args,
                    )
                finally:
                    self._batch_preflighted = False
                done += 1
        except Exception as e:
            # surface the per-window failure cause as STRUCTURE, not one
            # opaque sync-point exception (the serving tier's blast-
            # radius containment input, serve/resilience.py):
            # applied_iters = iterations that completed dispatch before
            # the failure, clean = the failed residue was never queued
            # to any lane (the dispatch preflight raised — see
            # _dispatch_fused), so re-dispatching it is bit-exact.  A
            # per-call iteration failing, or a submit-loop failure after
            # the preflight, is NOT clean: lanes may have diverged.
            if isinstance(e, InjectedFaultError):
                cause = f"injected:{e.point}"
            else:
                cause = type(e).__name__
            raise FusedBatchError(
                cause=cause, applied_iters=done, requested_iters=iters,
                clean=bool(getattr(e, "_ck_clean_window", False)),
                original=e,
            ) from e
        out = {
            "iters": iters,
            "fused": ladder > 0,
            "ladder_iters": ladder,
            "per_call_iters": iters - ladder,
        }
        if probe_cache:
            from .compilecache import probe_counts

            hits1, misses1 = probe_counts()
            out["cache_hits"] = hits1 - hits0
            out["cache_misses"] = misses1 - misses0
        return out

    # -- AOT warmup / persistent executable cache (ROADMAP item 4) -----------
    def _warm_targets(self) -> list:
        """Distinct (platform, donate, device_kind, device) combinations
        across this scheduler's lanes — the set of fused-launcher key
        variants the live path can request.  ``donate`` is the lane's
        own ``Worker.fused_donate``: a warmed key that differs in any
        component is a silent no-op."""
        seen: dict = {}
        for w in self.workers:
            platform = w.device.platform
            donate = w.fused_donate
            kind = str(getattr(w.device, "device_kind", platform))
            seen.setdefault((platform, donate, kind), w.device)
        return [(p, d, k, dev) for (p, d, k), dev in seen.items()]

    def warmup(self, plan) -> dict:
        """AOT-precompile a workload plan's full predicated launch
        ladders BEFORE traffic arrives (the first-class warmup path —
        ``ServeFrontend.warmup``, the fabric's warm-on-join, and the
        elastic rejoin all route here).

        ``plan`` is an iterable of :class:`~.compilecache.WarmupSpec`
        (or anything with the job surface ``kernels/params/global_range/
        local_range/values`` — e.g. ``serve.ServeJob``; live params are
        read for size/dtype only, NEVER executed against).  Per distinct
        spec, per distinct lane (platform, donate) variant, this builds
        and EXECUTES on scratch buffers:

        - the fused predicated-ladder executable under the EXACT key the
          live fused window requests (``KernelProgram.fused_launcher``
          9-tuple — executing it also fills jax's in-process dispatch
          cache, so the first live call is a cache hit end to end), and
        - every per-call chunk launcher ``step·2^k`` up to the global
          range (any balancer split's per-lane ladder is a subset).

        With ``CK_COMPILE_CACHE`` armed, each spec's ladder key is
        looked up in the on-disk manifest (hit/miss counted +
        ``ck_compile_cache_*`` metrics), misses are persisted for other
        processes, and the XLA compiles triggered here are served from /
        written to JAX's persistent compilation cache — a joining shard
        warms from disk instead of recompiling.  Unarmed, the disk layer
        is skipped entirely and results stay bit-identical.

        Emits one ``cache-warmup`` flight event + context decision per
        plan (key set, hit/miss split, wall).  Returns ``{"warmed",
        "hits", "misses", "skipped", "wall_s"}``."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from .compilecache import CACHE, WarmupSpec

        t0 = time.perf_counter()
        if CACHE.enabled:
            CACHE.arm()
        specs: list = []
        seen_specs: set = set()
        skipped = 0
        for item in plan:
            if isinstance(item, WarmupSpec):
                spec = item
            else:
                try:
                    spec = WarmupSpec.from_job(
                        item.kernels, item.params,
                        getattr(item, "compute_id", 0), item.global_range,
                        item.local_range,
                        getattr(item, "global_offset", 0),
                        getattr(item, "values", ()),
                    )
                except Exception:  # noqa: BLE001 - unwarmable job shape
                    skipped += 1
                    continue
            ident = (spec.kernels, spec.params, spec.global_range,
                     spec.local_range, spec.values)
            if ident in seen_specs:
                continue
            seen_specs.add(ident)
            if (spec.local_range <= 0
                    or spec.global_range % spec.local_range != 0
                    or not all(n in self.program for n in spec.kernels)):
                skipped += 1
                continue
            specs.append(spec)

        hits = misses = 0
        keys: list[str] = []
        # per-device-kind ladder count: the mixed-fleet warmup proof —
        # every kind present in the lane set gets its own AOT pass
        kinds: dict[str, int] = {}
        for spec in specs:
            step = spec.local_range
            units = spec.global_range // step
            vals = spec.value_args()

            def vals_for(name, _v=vals):
                if isinstance(_v, dict):
                    return tuple(_v.get(name, ()))
                return tuple(_v)

            for platform, donate, device_kind, device in self._warm_targets():
                kinds[device_kind] = kinds.get(device_kind, 0) + 1
                key = None
                hit = False
                if CACHE.enabled:
                    key = CACHE.ladder_key(
                        self.program, spec, platform, donate, device_kind)
                    keys.append(key)
                    hit = CACHE.lookup(key)
                bufs = tuple(
                    jnp.zeros(n, dtype=np.dtype(d), device=device)
                    for n, d in spec.params
                )
                # the fused predicated ladder, under the live path's key
                fn = self.program.fused_launcher(
                    tuple(spec.kernels), step, spec.global_range,
                    spec.local_range, spec.global_range, vals,
                    platform=platform, donate=donate,
                )
                if fn is not None:
                    # the run-time scalars as the live path hands them
                    # over (Worker.ladder_scalars): int32 arrays on the
                    # lane's device, the argument types of the executable
                    out = fn(*(jax.device_put(np.int32(v), device)
                               for v in (0, units, 1)), bufs)
                    jax.block_until_ready(out)
                    bufs = tuple(out)  # donate consumed the scratch set
                # every per-call chunk the binary ladder can emit
                nbits = max(1, units.bit_length())
                for name in dict.fromkeys(spec.kernels):
                    n_arr = self.program.array_param_count(name)
                    va = vals_for(name)
                    for k in range(nbits):
                        chunk = step << k
                        if chunk > spec.global_range:
                            break
                        try:
                            f2, _info = self.program.launcher(
                                name, chunk, spec.local_range,
                                spec.global_range, platform)
                            jax.block_until_ready(
                                f2(0, bufs[:n_arr], va))
                        except TypeError:
                            break  # unhashable static values: skip name
                if CACHE.enabled:
                    if hit:
                        hits += 1
                    else:
                        misses += 1
                        CACHE.record(key, spec, platform, donate,
                                     device_kind)
        wall_s = time.perf_counter() - t0
        FLIGHT.event(
            "cache-warmup", warmed=len(specs), hits=hits, misses=misses,
            skipped=skipped, wall_ms=round(wall_s * 1e3, 3),
            cache=CACHE.enabled, kinds=dict(kinds),
        )
        if DECISIONS.enabled:
            # context record (reads the filesystem: provenance, not
            # oracle) — which keys this plan warmed, from which split
            DECISIONS.record("cache-warmup", {
                "specs": [s.to_payload() for s in specs],
                "cache_enabled": CACHE.enabled,
                "cache_root": CACHE.root,
            }, {
                "warmed": len(specs), "hits": hits, "misses": misses,
                "skipped": skipped, "keys": keys,
                "wall_ms": round(wall_s * 1e3, 3),
                "kinds": dict(kinds),
            })
        return {"warmed": len(specs), "hits": hits, "misses": misses,
                "skipped": skipped, "wall_s": wall_s,
                "kinds": dict(kinds)}

    def _cache_record_engaged(self, run: _FusedRun) -> None:
        """Persist an engaged window's ladder spec so OTHER processes
        can warm it from disk (the fleet's live signature mix IS the
        cache's content).  Cold path — once per distinct key per
        process (the ``_seen`` set bounds disk probes); best-effort and
        torn-tolerant like every cache write."""
        from .compilecache import CACHE, WarmupSpec

        try:
            spec = WarmupSpec.from_job(
                run.kernel_names, run.params, run.compute_id,
                run.global_range, run.local_range, 0, run.value_args)
            for platform, donate, device_kind, _dev in self._warm_targets():
                key = CACHE.ladder_key(
                    self.program, spec, platform, donate, device_kind)
                if key in CACHE._seen:
                    continue
                if not CACHE.lookup(key, count=False):
                    CACHE.record(key, spec, platform, donate, device_kind)
        except Exception:  # noqa: BLE001 - cache is never load-bearing
            pass

    def _fused_drain(self) -> None:
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

    # -- per-worker phase (reference: Cores.cs:746-835 / 1197-1980) ----------
    def _run_worker(
        self,
        w: Worker,
        kernel_names: Sequence[str],
        params: Sequence[ClArray],
        compute_id: int,
        offset: int,
        size: int,
        local_range: int,
        global_range: int,
        pipeline: bool,
        blobs: int,
        pipeline_type: int,
        value_args,
        write_all_owner: dict[int, int],
        plan=None,
    ) -> None:
        gate = self.dispatch_gate
        if gate is not None:
            # ckcheck: ok user-triggered gate — blocking until the
            # caller fires it IS the ClUserEvent synchronized-start
            # semantic (reference: Worker.cs:487-557)
            gate.wait()
        # serialize whole phases per worker: concurrent host threads driving
        # DIFFERENT compute ids through one Cores (the reference's
        # kernelWithId concurrency contract, Worker.cs:291-316) otherwise
        # interleave read-modify-write on the worker's buffer/coverage
        # dicts.  The bench starts after acquisition so one id's measured
        # time never includes waiting on another id's phase.
        with w.lock:
            self._run_worker_locked(
                w, kernel_names, params, compute_id, offset, size,
                local_range, global_range, pipeline, blobs, pipeline_type,
                value_args, write_all_owner, plan,
            )
        # from here on a caller still inside its join waits for another lane
        TRACER.instant("enqueue", cid=compute_id, lane=w.index,
                       tag="phase-done")

    def _run_worker_locked(
        self,
        w: Worker,
        kernel_names: Sequence[str],
        params: Sequence[ClArray],
        compute_id: int,
        offset: int,
        size: int,
        local_range: int,
        global_range: int,
        pipeline: bool,
        blobs: int,
        pipeline_type: int,
        value_args,
        write_all_owner: dict[int, int],
        plan=None,
    ) -> None:
        w.start_bench(compute_id)
        single = self.num_devices == 1
        try:
            if pipeline and blobs > 1:
                engine = (
                    self._run_pipelined_event
                    if pipeline_type == PIPELINE_EVENT
                    else self._run_pipelined_driver
                )
                engine(
                    w, kernel_names, params, compute_id, offset, size,
                    local_range, global_range, blobs, value_args, single,
                    write_all_owner,
                )
                return
            # a phase with a plan (its kernels read across lanes:
            # _stage_exchange) never streams: a chunk's launch would read
            # the rows of the chunk behind it before they were uploaded
            streamed, key_bytes = self._run_streamed(
                w, kernel_names, params, compute_id, offset, size,
                local_range, global_range, value_args, single,
                write_all_owner,
            ) if plan is None else (False, None)
            if streamed:
                return  # chunked wavefront handled the phase
            t_phase0 = time.perf_counter()
            # key_bytes is _run_streamed's own bytes key for this phase
            # (one formula, computed once).  None means streaming was
            # off or could not apply — then the tuner neither measures
            # nor observes: the phase can never stream, and with the
            # kill switch off the monolithic path must not pay key
            # computation or the tuner lock at all.
            tuner_key = (
                self._tuner_kernel_key(kernel_names, value_args)
                if key_bytes else None
            )
            # the tuner's MEASURING run (first contact for this key):
            # pay one fence after the launches so the wall splits into
            # honest phases — without it the async launches retire
            # inside the D2H timing window and C lands in D, leaving
            # the model a (U, ~0, C+D) estimate that under-chunks
            measuring = (
                tuner_key is not None
                and not self.no_compute_mode
                and not self.transfer_tuner.has_obs(
                    w.index, tuner_key, key_bytes
                )
            )
            # H2D — t_up_stream times only the CHUNK-STREAMABLE uploads
            # (partial_read partitions, the ones _stream_key_bytes
            # counts): whole-array uploads of non-partial operands are
            # serial in the streamed path too (up-front, un-hideable),
            # so their wall must land in the tuner's C, not its U — a U
            # inflated by un-hideable bytes over-credits chunking and
            # mis-learns every lane's per-chunk overhead
            t_up = 0.0
            t_up_stream = 0.0
            for idx, p in enumerate(params if plan is None else ()):
                fl = p.flags
                if fl.read and not fl.write_only:
                    epw = fl.elements_per_work_item
                    full = single or not fl.partial_read
                    if self.enqueue_mode and w.upload_covers(
                        p, 0 if full else offset * epw, p.size if full else size * epw
                    ):
                        continue  # data lives in HBM across enqueued computes
                    t0u = time.perf_counter()
                    w.upload(p, offset * epw, size * epw, full)
                    dt_u = time.perf_counter() - t0u
                    t_up += dt_u
                    if fl.partial_read:
                        t_up_stream += dt_u
                else:
                    w.ensure_resident(p)
            if plan is not None:
                t_up = self._make_current(w, params, plan, compute_id)
            # compute
            if not self.no_compute_mode:
                if plan is not None and self.enqueue_mode \
                        and self.fused_dispatch:
                    # a window's compute that could not be deferred rides
                    # the ladder executable all the same, one pass of it:
                    # ONE dispatch a lane whatever the rungs of its range,
                    # stores written into the lane's buffers in place
                    # (launch_fused falls back to the per-rung loop where
                    # the values do not hash)
                    w.launch_fused(
                        self.program, kernel_names, params, value_args,
                        offset, size, local_range, global_range,
                        local_range, 1, compute_id=compute_id,
                        reach=plan.reach,
                    )
                else:
                    w.launch(
                        self.program, kernel_names, params, value_args,
                        offset, size, local_range, global_range, local_range,
                        repeats=self.repeat_count,
                        sync_kernel=self.repeat_sync_kernel,
                        compute_id=compute_id,
                        reach=plan.reach if plan is not None else "",
                    )
                if measuring:
                    w.fence()
            t_dispatched = time.perf_counter() if self.trace_lanes else 0.0
            # D2H
            handles = []
            for idx, p in enumerate(params):
                fl = p.flags
                if not (fl.write and not fl.read_only):
                    continue
                if self.enqueue_mode:
                    # write_all: only the owning chip defers a readback, same
                    # ownership rule as the immediate paths
                    if not fl.write_all or w.index == write_all_owner.get(idx):
                        with self._lock:
                            self._defer_readback(
                                w, p, offset, size, compute_id)
                    continue
                epw = fl.elements_per_work_item
                if fl.write_all:
                    # whole-array write: only the owning chip writes it back
                    if w.index == write_all_owner.get(idx):
                        handles.append(w.download_async(p, 0, p.size, True))
                else:
                    # full (no-slice) download only when the range covers the
                    # whole array — else it would overwrite host elements the
                    # kernel never touched
                    covers = offset == 0 and size * epw == p.size
                    full = single and not _any_partial(params) and covers
                    handles.append(
                        w.download_async(p, offset * epw, size * epw, full)
                    )
            t0d = time.perf_counter()
            for h in handles:
                Worker.finish_download(h)
            t_down = time.perf_counter() - t0d if handles else 0.0
            self._note_transfer(
                w, tuner_key, compute_id, key_bytes or 0, t_up, t_down,
                time.perf_counter() - t_phase0, fenced=measuring,
                u_tune_s=t_up_stream,
            )
            if self.trace_lanes:
                with self._lock:
                    self.lane_trace.setdefault(compute_id, []).append(
                        (w.index, t_dispatched, time.perf_counter())
                    )
        finally:
            w.end_bench(compute_id)

    def _make_current(self, w: Worker, params: Sequence[ClArray],
                      plan: _LanePlan, compute_id: int) -> float:
        """A lane's half of :meth:`_stage_exchange`, under its phase lock:
        lay in what was staged for it (a synchronous compute's uploads;
        the strips cut from the lanes that wrote them last: one ``halo``
        span a compute that fetched any) and, inside an enqueue window,
        upload what its coverage lacks of the intervals no lane holds.
        Returns the seconds the uploads took."""
        t0 = time.perf_counter()
        for staged in plan.uploads:
            w.commit_upload(staged)
        for idx, p in enumerate(params):
            if idx not in plan.host:
                w.ensure_resident(p)
            elif self.enqueue_mode:  # (a synchronous compute staged them)
                for lo, hi in plan.host[idx]:
                    if not w.upload_covers(p, lo, hi - lo):
                        w.upload(p, lo, hi - lo, (lo, hi) == (0, p.size))
        t_up = time.perf_counter() - t0
        if plan.strips:
            _th = TRACER.t0("halo")
            how = {w.lay_strip(src, p, strip, lo)
                   for src, p, strip, lo in plan.strips}
            if _th:
                TRACER.record(
                    "halo", _th, cid=compute_id, lane=w.index,
                    tag="+".join(sorted(how)),
                    bytes=sum(s[2].nbytes for s in plan.strips),
                    src="+".join(str(k) for k in sorted(
                        {s[0].index for s in plan.strips})))
        return t_up

    def _stream_key_bytes(
        self, w: Worker, params: Sequence[ClArray], offset: int, size: int,
        single: bool,
    ) -> int:
        """Partition-transfer byte count of one phase under the STREAM
        classification — the ONE formula both the autotuner's ``choose``
        key and its ``observe`` key ride (two formulas would land the
        measuring run's observation in a different power-of-two bucket
        than the lookup, leaving the key in a perpetual measuring run
        and the streamed path silently dead).  Counts the phase's
        chunk-streamable bytes: uncovered partial-read uploads plus
        immediate ranged downloads (full-array uploads are not partition
        transfers; enqueue-mode downloads are the flush's business).
        Must run BEFORE the phase's uploads — they change coverage."""
        nbytes = 0
        for p in params:
            fl = p.flags
            epw = fl.elements_per_work_item
            if fl.read and not fl.write_only and fl.partial_read:
                # mirrors _run_streamed's up_parts test: on a single
                # device the range IS the whole array
                if not (self.enqueue_mode and w.upload_covers(
                        p, 0 if single else offset * epw,
                        p.size if single else size * epw)):
                    nbytes += epw * size * p.host().dtype.itemsize
            if (not self.enqueue_mode and fl.write and not fl.read_only
                    and not fl.write_all):
                nbytes += epw * size * p.host().dtype.itemsize
        return nbytes

    @staticmethod
    def _tuner_kernel_key(kernel_names, value_args) -> tuple:
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

    def _note_transfer(
        self, w: Worker, tuner_key, compute_id: int, nbytes: int,
        u_s: float, d_s: float, wall_s: float, chunks: int = 1,
        fenced: bool = False, u_tune_s: float | None = None,
    ) -> None:
        """Record one phase's measured transfer split: the per-cid
        transfer bench (telemetry here — in immediate paths it is a
        subset of the same wall the compute bench carries, so the
        balancer floor binds at the enqueue FLUSH drain, see
        ``_finish_deferred``), and (when the phase was a streaming
        candidate — ``tuner_key`` not None — and moved partition bytes)
        a tuner observation: FENCED monolithic runs teach the model its
        honest U/C/D for this (lane, kernel+values, bytes) point,
        unfenced ones only clamp (their async launches retire inside the
        D2H window, so the split is contaminated), chunked runs refine
        the lane's real per-chunk overhead.  ``tuner_key`` None means
        the phase can never stream (or the kill switch is off): the
        tuner lock is not taken at all.  ``nbytes`` is the
        ``_stream_key_bytes`` value of the SAME phase.  ``u_tune_s``
        restricts the tuner's U to the CHUNK-STREAMABLE uploads when
        the phase also moved whole-array operands (those are serial in
        the streamed path too — their wall belongs in C); the balancer
        floor keeps the TOTAL u_s."""
        u_ms, d_ms = u_s * 1000.0, d_s * 1000.0
        if u_s + d_s > 0.0 and not self.enqueue_mode:
            # lane health: only phases that MOVED bytes feed the rolling
            # transfer baseline — and only on the IMMEDIATE path, where
            # one call = one iteration so the phase wall is already on
            # the signal's per-iteration scale.  In enqueue mode the
            # flush drain owns this signal (same ownership rule as the
            # transfer_benchmarks dict below): an in-window phase is
            # per-WINDOW scaled (a post-coverage-reset re-upload serves
            # N iterations at once) and would corrupt the baseline the
            # drain's normalized samples establish
            self.health.observe(w.index, "transfer", u_s + d_s)
        if not self.enqueue_mode:
            # immediate path: one call = one iteration, so the phase
            # wall is unit-consistent with the per-call compute bench.
            # In ENQUEUE mode the flush drain owns this dict — its
            # values are per-ITERATION (divided by the window's count,
            # _finish_deferred); an in-window phase wall is per-WINDOW
            # scaled (a post-coverage-reset phase re-uploads the whole
            # partition once for N iterations) and steady covered
            # phases are 0.0 — either write would corrupt the floor
            # the next rebalance reads
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

    def _run_streamed(
        self,
        w: Worker,
        kernel_names: Sequence[str],
        params: Sequence[ClArray],
        compute_id: int,
        offset: int,
        size: int,
        local_range: int,
        global_range: int,
        value_args,
        single: bool,
        write_all_owner: dict[int, int],
    ) -> tuple[bool, int | None]:
        """STREAM engine — the chunked double-buffered partition
        transfer path.  Returns ``(handled, key_bytes)``: ``handled``
        False means the caller falls through to the monolithic path
        (the identity fallback) — streaming could not apply or the
        autotuner picked 1 chunk; ``key_bytes`` is the phase's
        ``_stream_key_bytes`` value when it was computed (the phase IS
        a streaming candidate — the monolithic fallback uses it for the
        tuner's measuring run and observation) and None when streaming
        was gated off before the key existed (then the monolithic path
        must not pay key computation or the tuner lock at all — the
        kill-switch contract).

        The lane's timeline becomes a true read/compute/write pipeline:
        the CALLER thread is the transfer lane — it stages chunk j's H2D
        (the DMA starts immediately) and submits chunk j's closure
        (commit + ladder launch + D2H issue) to the per-worker stream
        driver, whose depth (``stream_queue_depth``, default 2) bounds
        how far staging runs ahead of dispatch — the double buffer.
        Chunks are ``step·2^k`` (``chunk_plan``), so every chunk launch
        hits the compile-once ladder executables; the kernel sequence
        stays KERNEL-MAJOR exactly like ``Worker.launch`` (kernel k
        covers the whole range, ascending offsets, before kernel k+1),
        so results are bit-identical to the monolithic path — the only
        thing that moves is WHEN transfers are issued.  Uploads
        interleave with the FIRST kernel's chunk launches, downloads
        with the LAST kernel's (one kernel: both in one wavefront);
        middle kernels launch whole-range.

        Runs under the worker's phase lock (the caller holds it), which
        is why the stream-driver closures never take worker locks — see
        ``Worker.stream_dispatch_async``."""
        if (
            not self.streamed_transfers
            or self.no_compute_mode
            or self.repeat_count > 1
            or self.repeat_sync_kernel
            or self.trace_lanes
        ):
            return False, None
        step = local_range
        max_chunks = size // step if step > 0 else 0
        if max_chunks < 2:
            return False, None
        # classify the phase's transfers exactly like the monolithic path
        up_parts: list[ClArray] = []   # chunk-streamed partition uploads
        up_full: list[ClArray] = []    # whole-array uploads (up-front)
        ensure: list[ClArray] = []
        # arrays a kernel stores to OUTSIDE the work item's own elements (a
        # scattered store): a chunk's launch may write another chunk's
        # elements, so they go up whole before the first launch and come
        # back after the last, never chunk by chunk
        roam = self.program.roaming_stores(
            tuple(kernel_names),
            tuple(p.flags.elements_per_work_item for p in params))
        for idx, p in enumerate(params):
            fl = p.flags
            if fl.read and not fl.write_only:
                epw = fl.elements_per_work_item
                full = single or not fl.partial_read
                if self.enqueue_mode and w.upload_covers(
                    p, 0 if full else offset * epw, p.size if full else size * epw
                ):
                    continue  # resident across enqueued computes
                # a PARTIAL-read array chunk-streams over the lane's
                # range even on a single device (there the range IS the
                # whole array, so ranged chunks == the full upload);
                # non-partial arrays must land whole before any launch
                # (the kernel may read outside the lane's range)
                (up_parts if fl.partial_read and idx not in roam
                 else up_full).append(p)
            else:
                ensure.append(p)
        down_parts: list[tuple[int, ClArray]] = []
        down_late: list[ClArray] = []  # ranged, after the last launch
        if not self.enqueue_mode:
            for idx, p in enumerate(params):
                fl = p.flags
                if fl.write and not fl.read_only and not fl.write_all:
                    if idx in roam:
                        down_late.append(p)
                    else:
                        down_parts.append((idx, p))
        if not up_parts and not down_parts:
            # nothing to overlap — monolithic path is exact
            return False, None
        nbytes = self._stream_key_bytes(w, params, offset, size, single)
        tuner_key = self._tuner_kernel_key(kernel_names, value_args)
        chunks = self.stream_chunks
        if not chunks:
            _tu = TRACER.t0("tune")
            chunks = self.transfer_tuner.choose(
                w.index, tuner_key, nbytes, max_chunks
            )
            if _tu:
                TRACER.record("tune", _tu, cid=compute_id, lane=w.index,
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
                TRACER.instant("tune", cid=compute_id, lane=w.index,
                               tag=f"chunks:{was}->{chunks}")
        self.last_stream_chunks[w.index] = chunks
        w.m_chunk_count.set(chunks)
        if chunks <= 1:
            return False, nbytes
        plan = chunk_plan(size, step, chunks)
        _tt = TRACER.t0("pipeline-stage")
        t_phase0 = time.perf_counter()
        for p in up_full:
            w.upload(p, 0, p.size, True)
        for p in ensure:
            w.ensure_resident(p)
        handles: list = []
        stage_s = [0.0]
        stall_s = [0.0]   # backpressure waits in stream_dispatch_async
        n_submits = [0]   # the stall normalizer: actual submits made
        depth = max(1, int(self.stream_queue_depth))
        names = list(kernel_names)
        last = len(names) - 1
        try:
            for ki, name in enumerate(names):
                do_up = bool(up_parts) and ki == 0
                do_down = bool(down_parts) and ki == last
                if not do_up and not do_down:
                    # middle kernels: plain whole-range ladder (nothing
                    # to overlap with — operands are already resident)
                    w.launch(
                        self.program, [name], params, value_args, offset,
                        size, local_range, global_range, local_range,
                        compute_id=compute_id,
                    )
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
                        stage_s[0] += time.perf_counter() - t0s

                    def run_chunk(
                        name=name, boff=boff, csz=csz, staged=staged,
                        do_down=do_down,
                    ):
                        for s in staged:
                            w.commit_upload(s)
                        w.launch(
                            self.program, [name], params, value_args,
                            boff, csz, local_range, global_range,
                            local_range, compute_id=compute_id,
                        )
                        if do_down:
                            for _idx, p in down_parts:
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
                        preflighted=self._batch_preflighted)
                    stall_s[0] += time.perf_counter() - t0q
                    n_submits[0] += 1
                w.drain_stream_dispatch()
        except BaseException:
            # closures must never outlive the phase lock the caller
            # holds; the primary error outranks any drain follow-up
            try:
                w.drain_stream_dispatch()
            except Exception:  # noqa: BLE001 - primary error wins
                pass
            raise
        if self.enqueue_mode:
            # deferred-readback records at the SAME granularity as the
            # monolithic path (one record per array; flush() chunks the
            # drain itself)
            for idx, p in enumerate(params):
                fl = p.flags
                if fl.write and not fl.read_only:
                    if not fl.write_all or w.index == write_all_owner.get(idx):
                        with self._lock:
                            self._defer_readback(
                                w, p, offset, size, compute_id)
        else:
            for idx, p in enumerate(params):
                fl = p.flags
                if fl.write and not fl.read_only and fl.write_all:
                    if w.index == write_all_owner.get(idx):
                        handles.append(w.download_async(p, 0, p.size, True))
            for p in down_late:
                epw = p.flags.elements_per_work_item
                handles.append(
                    w.download_async(p, offset * epw, size * epw, False))
        t0d = time.perf_counter()
        for h in handles:
            Worker.finish_download(h)
        t_down = time.perf_counter() - t0d if handles else 0.0
        wall_s = time.perf_counter() - t_phase0
        self._note_transfer(
            w, tuner_key, compute_id, nbytes, stage_s[0], t_down,
            wall_s, chunks=len(plan),
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
            w.index, "stream_stall", stall_s[0] / max(1, n_submits[0]))
        TRACER.record(
            "pipeline-stage", _tt, cid=compute_id, lane=w.index,
            tag=f"STREAM x{len(plan)}",
        )
        return True, nbytes

    def _pipeline_prologue(
        self, w: Worker, params: Sequence[ClArray], offset: int, size: int
    ):
        """Shared per-call setup for both pipeline engines: residency
        snapshot + up-front upload of non-blobbed arrays."""
        # enqueue mode: snapshot residency BEFORE any uploads — a buffer
        # created by blob 1 must not suppress blobs 2..N of the same call.
        # Coverage is range-aware: a partial array whose chip range MOVED at
        # the last sync-point rebalance is not "resident" and re-uploads.
        resident = set()
        if self.enqueue_mode:
            for p in params:
                epw = p.flags.elements_per_work_item
                covered = (
                    w.upload_covers(p, offset * epw, size * epw)
                    if p.flags.partial_read
                    else w.upload_covers(p, 0, p.size)
                )
                if covered:
                    resident.add(id(p))
        # non-blobbed arrays (not partial) upload once up-front
        for p in params:
            fl = p.flags
            reads = fl.read and not fl.write_only
            if reads and not fl.partial_read:
                if id(p) not in resident:
                    w.upload(p, 0, 0, True)
            elif not reads:
                w.ensure_resident(p)
        return resident

    def _pipeline_epilogue(
        self,
        w: Worker,
        params: Sequence[ClArray],
        compute_id: int,
        offset: int,
        size: int,
        write_all_owner: dict[int, int],
        handles: list,
    ) -> None:
        """Shared tail: write_all readbacks / enqueue-mode deferral, then
        join all in-flight D2H copies."""
        for idx, p in enumerate(params):
            fl = p.flags
            if not (fl.write and not fl.read_only):
                continue
            if fl.write_all:
                if w.index == write_all_owner.get(idx):
                    if self.enqueue_mode:
                        with self._lock:
                            self._defer_readback(w, p, 0, p.size, compute_id)
                    else:
                        handles.append(w.download_async(p, 0, p.size, True))
            elif self.enqueue_mode:
                with self._lock:
                    self._defer_readback(w, p, offset, size, compute_id)
        for h in handles:
            Worker.finish_download(h)

    def _run_pipelined_driver(
        self,
        w: Worker,
        kernel_names: Sequence[str],
        params: Sequence[ClArray],
        compute_id: int,
        offset: int,
        size: int,
        local_range: int,
        global_range: int,
        blobs: int,
        value_args,
        single: bool,
        write_all_owner: dict[int, int],
    ) -> None:
        """DRIVER engine: depth-first dispatch chains — blob k's full
        H2D → compute → D2H is issued back-to-back with no host
        synchronization, blob k+1's chain follows immediately (reference:
        the driver-driven 16-queue pipeline, blob k → queue k mod 16 doing
        R+C+W with no events, Cores.cs:1371-1858).  XLA's async dispatch
        streams play the role of the 16 in-order queues: the transfer
        engine runs blob k+1's DMA while the compute stream runs blob k."""
        _tt = TRACER.t0("pipeline-stage")
        blob = size // blobs
        if blob <= 0:
            blob, blobs = size, 1
        resident = self._pipeline_prologue(w, params, offset, size)
        handles = []
        for k in range(blobs):
            boff = offset + k * blob
            for p in params:
                fl = p.flags
                if fl.read and not fl.write_only and fl.partial_read:
                    if id(p) in resident:
                        continue
                    epw = fl.elements_per_work_item
                    w.upload(p, boff * epw, blob * epw, False)
            if not self.no_compute_mode:
                w.launch(
                    self.program, kernel_names, params, value_args,
                    boff, blob, local_range, global_range, local_range,
                    repeats=self.repeat_count, sync_kernel=self.repeat_sync_kernel,
                    compute_id=compute_id,
                )
            for idx, p in enumerate(params):
                fl = p.flags
                if fl.write and not fl.read_only and not fl.write_all:
                    if self.enqueue_mode:
                        continue  # deferred in the epilogue as one record
                    epw = fl.elements_per_work_item
                    handles.append(w.download_async(p, boff * epw, blob * epw, False))
        self._pipeline_epilogue(
            w, params, compute_id, offset, size, write_all_owner, handles
        )
        TRACER.record(
            "pipeline-stage", _tt, cid=compute_id, lane=w.index,
            tag=f"DRIVER x{blobs}",
        )

    def _run_pipelined_event(
        self,
        w: Worker,
        kernel_names: Sequence[str],
        params: Sequence[ClArray],
        compute_id: int,
        offset: int,
        size: int,
        local_range: int,
        global_range: int,
        blobs: int,
        value_args,
        single: bool,
        write_all_owner: dict[int, int],
    ) -> None:
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
        blob = size // blobs
        if blob <= 0:
            blob, blobs = size, 1
        look = max(1, int(self.pipeline_lookahead))
        resident = self._pipeline_prologue(w, params, offset, size)
        partials = [
            p
            for p in params
            if p.flags.read
            and not p.flags.write_only
            and p.flags.partial_read
            and id(p) not in resident
        ]
        writers = [
            (idx, p)
            for idx, p in enumerate(params)
            if p.flags.write and not p.flags.read_only and not p.flags.write_all
        ]
        staged: dict[int, list] = {}
        handles = []
        for j in range(blobs + look + 1):
            if j < blobs:  # read stage: start blob j's DMA
                boff = offset + j * blob
                staged[j] = [
                    w.stage_upload(
                        p,
                        boff * p.flags.elements_per_work_item,
                        blob * p.flags.elements_per_work_item,
                    )
                    for p in partials
                ]
            k = j - look
            if 0 <= k < blobs:  # compute stage: commit blob k, launch kernels
                for s in staged.pop(k, ()):
                    w.commit_upload(s)
                if not self.no_compute_mode:
                    w.launch(
                        self.program, kernel_names, params, value_args,
                        offset + k * blob, blob, local_range, global_range,
                        local_range, repeats=self.repeat_count,
                        sync_kernel=self.repeat_sync_kernel,
                        compute_id=compute_id,
                    )
            m = j - look - 1
            if 0 <= m < blobs and not self.enqueue_mode:  # write stage
                boff = offset + m * blob
                for idx, p in writers:
                    epw = p.flags.elements_per_work_item
                    handles.append(w.download_async(p, boff * epw, blob * epw, False))
        self._pipeline_epilogue(
            w, params, compute_id, offset, size, write_all_owner, handles
        )
        TRACER.record(
            "pipeline-stage", _tt, cid=compute_id, lane=w.index,
            tag=f"EVENT x{blobs} look{look}",
        )

    # -- enqueue-mode sync (reference: flushLastUsedCommandQueue / finish) ----
    @staticmethod
    def _latest_records(pending) -> list[tuple]:
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

    def _start_deferred_downloads(self, pending, lock_each: bool) -> list:
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
        ``(handle, worker, cid)`` entries for :meth:`_finish_deferred`
        in the records' CHRONOLOGICAL order, whatever the order of
        issue: finish order is the order of the host writes."""
        records = self._latest_records(pending)
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

    def _finish_deferred(self, entries, iters: dict[int, int]) -> None:
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

    def flush(self) -> None:
        """Read back and join everything deferred by enqueue mode.  Any
        open fused window is dispatched and drained first — the download
        slices must see the post-ladder buffers."""
        self._fused_close()
        _tr = TRACER.t0("resync")
        with self._lock:
            pending, self._enqueued = self._enqueued, []
            flush_iters, self._flush_iters = self._flush_iters, {}
        TRACER.instant("resync", tag="part:issue")
        entries = self._start_deferred_downloads(pending, lock_each=True)
        TRACER.instant("resync", tag="part:join")
        self._finish_deferred(entries, flush_iters)
        TRACER.record("resync", _tr, tag="flush")

    def _flush_and_reset_coverage(self) -> None:
        """The sync-point-rebalance flush: read back every deferred record
        AND reset every chip's upload coverage as ONE atomic step under
        ALL worker locks (the window-scoped coverage epoch the r7 KNOWN
        LIMIT deferred).

        Why atomicity matters: with several host threads enqueuing
        different cids, a plain flush-then-reset lets another thread's
        window launch between the flush's host writes and the coverage
        reset — that thread's next covered-range check then re-uploads a
        host copy missing its own just-launched increments (lost updates,
        10-12/12 arrays on the 2-lane rig at seed).  Holding every worker
        lock across [collect → download → host write → reset] makes the
        interleaving structurally impossible: any launch sequenced before
        the block has its record collected here (records are appended
        under the worker lock), and any launch after the block sees reset
        coverage AND a host already made current.  Each reset bumps
        Worker.coverage_epoch, which in-flight fused windows check per
        deferral (compute() breaks them with reason "non-resident").

        Lock order is safe: no other path holds two worker locks, and
        this thread takes the scheduler lock only nested inside (matching
        _run_worker_locked's order)."""
        self._fused_close()
        TRACER.instant("resync", tag="part:locks")
        with ExitStack() as stack:
            for w in self.workers:
                stack.enter_context(w.lock)
            TRACER.instant("resync", tag="part:issue")
            with self._lock:
                pending, self._enqueued = self._enqueued, []
                flush_iters, self._flush_iters = self._flush_iters, {}
            entries = self._start_deferred_downloads(pending, lock_each=False)
            TRACER.instant("resync", tag="part:join")
            self._finish_deferred(entries, flush_iters)
            TRACER.instant("resync", tag="part:reset")
            for w in self.workers:
                w.reset_coverage()

    # -- introspection plane (obs/) ------------------------------------------
    def serve_debug(self, port: int = 0, host: str = "127.0.0.1"):
        """Start the live debug HTTP server (obs/debugserver.py) over
        this scheduler: ``/metrics``, ``/statusz``, ``/tracez``,
        ``/healthz``, ``/flightz`` on a daemon thread.  ``port=0``
        binds an ephemeral port — read it from the returned server's
        ``.port``.  Idempotent per Cores: a second call returns the
        already-running server."""
        if self._debug_server is None:
            from ..obs.debugserver import DebugServer

            self._debug_server = DebugServer(self, port=port, host=host)
            FLIGHT.event("debug-server", port=self._debug_server.port)
        return self._debug_server

    def health_report(self) -> dict:
        """Per-lane health verdicts (``obs/health.py``): ``{lane:
        {"verdict": ok|suspect|degraded, "score", "evidence"}}``.
        Advisory — ``health.suggest_drain()`` names degraded lanes,
        nothing here acts on them."""
        return self.health.report()

    def _lane_config(self) -> dict:
        """The postmortem's lane block: enough static configuration to
        read a dump without the process that wrote it."""
        return {
            "devices": self.device_names(),
            "ranges": {
                str(cid): list(r) for cid, r in self.global_ranges.items()
            },
            "enqueue_mode": self.enqueue_mode,
            "fused_dispatch": self.fused_dispatch,
            "streamed_transfers": self.streamed_transfers,
            # ckcheck: ok racy snapshot copy — reporting only
            "stream_chunks": dict(self.last_stream_chunks),
        }

    # -- reporting -----------------------------------------------------------
    def performance_report(self, compute_id: int | None = None) -> str:
        cid = compute_id if compute_id is not None else self.last_compute_id
        if cid is None or cid not in self.perf:
            return "(no compute has run)"
        text = self.perf[cid].report(self.device_names())
        return text

    def benchmarks_of(self, compute_id: int) -> list[float]:
        # ckcheck: ok racy bench read — reporting only
        return [w.benchmarks.get(compute_id, 0.0) for w in self.workers]

    def performance_history(self, compute_id: int) -> list[ComputePerf]:
        return list(self.perf_log.get(compute_id, ()))

    def barrier(self) -> None:
        """Block until all dispatched device work has retired WITHOUT
        reading results back (enqueue-mode sync point; the reference's
        finish() on the used queues, Worker.cs:364-423).

        Each chip is fenced by ONE ``block_until_ready`` over its cached
        buffers (see Worker.fence), and the chips are fenced concurrently
        so each lane's retire time is measured from the same window start.

        A device/kernel failure surfacing at the fence is REAL — it is
        collected per worker and the first one re-raised after all workers
        have been fenced (a swallowed error here would let a failed
        dispatch masquerade as a fast, wrong benchmark).

        Enqueue-mode balancing happens HERE: each chip's fence-retire time
        since the dispatch window opened is the chip's measured backlog —
        that is fed into its benchmark for every compute id dispatched since
        the last barrier, and those ids are armed to rebalance on their next
        call (sync-granularity analogue of the reference feeding event
        benches into loadBalance, HelperFunctions.cs:190-280).

        Mixed-window attribution: by default the whole-window fence time
        is assigned as the bench of EVERY compute id dispatched in the
        window — when kernels with different per-chip cost profiles
        share one enqueue window, each id's bench includes the others'
        work and a subsequent armed rebalance can misattribute cost
        between them.  Ids dispatched in homogeneous windows (one kernel
        per window — the common pattern) are measured exactly either
        way.  With :attr:`fence_split` on, the barrier instead fences
        each compute id's LAST launch output in last-dispatch order and
        feeds the balancer MARGINAL per-cid times
        (trace/attribution.split_fence_benches): batched mixed windows
        (all of id A, then all of id B) are then measured exactly per
        id, at the cost of one extra completion wait per id in
        the window; interleaved windows remain bounded by stream order
        (a cid's marginal includes earlier-dispatched work of
        later-completing ids).

        Fused windows close HERE: pending deferred iterations dispatch
        (one ladder per device through the driver queues) and the drivers
        drain before the fence, so the fence-retire time covers them —
        window-granularity rebalance feedback, normalized to
        per-iteration benches (balance.per_iteration_benches) so windows
        of different sizes feed the balancer one scale."""
        self._fused_close()
        # cached handle (constructor): the barrier is every window's
        # fence — a registry get-or-create per window is window_fence
        # residue (r7 attribution)
        self._m_barriers.inc()
        _mt0 = time.perf_counter()
        t_b = TRACER.t0("fence")
        # ONE consistent snapshot of the window state under the lock:
        # another host thread's compute() mutates t0 / the cid order /
        # the iteration counts mid-barrier, and the previous unlocked
        # point reads could see a half-updated window (cid added to the
        # set, iteration count not yet bumped) and feed the balancer a
        # mismatched divisor (ckcheck lockset finding, PR 7)
        with self._lock:
            t0 = self._enqueue_t0
            window_cids = set(self._enqueue_cids)
            window_cid_order = list(self._enqueue_cid_order)
            window_iters_map = dict(self._enqueue_iters)
            # a compute id whose computes exchanged: every step of a lane
            # waits for the rows its neighbours wrote the step before, so
            # the lanes retire TOGETHER whatever their shares.  Their fence
            # times say nothing about one lane's rate (read as rates they
            # call the lane with the most items the fastest and give it
            # more: PERF.md, PR 32): such an id keeps its benches and arms
            # no rebalance here; a synchronous compute, whose lanes run
            # each for itself, still moves its ranges.
            balanced_cids = window_cids - self._exchanging
        measure = self.enqueue_mode and t0 is not None and len(self.workers) > 1
        split_order = (
            window_cid_order
            if (self.fence_split and measure and len(window_cids) > 1)
            else []
        )
        try:
            TRACER.instant("fence", tag="part:wait")
            if len(self.workers) == 1:
                self.workers[0].fence()
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
                    rng = self.global_ranges.get(cid)
                    if rng is not None and rng[w.index] <= 0:
                        continue  # this chip never ran the id
                    if w.fence_cid(cid):
                        comps.append((cid, time.perf_counter()))
                w.fence()
                done_at[w.index] = time.perf_counter()
                comp_at[w.index] = comps

            errs: list[Exception] = []
            futs = [self.pool.submit(TRACER.bind(fence_timed, w.index), w)
                    for w in self.workers]
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
                # ROADMAP's eviction loop keys on.  Normalized by the
                # window's total iteration count, same scale rule as the
                # benches below and the transfer signal: a workload that
                # grows its window 4x is not a 4x-slower lane, and an
                # un-normalized feed would flip EVERY lane degraded on a
                # pure cadence change
                window_iters = max(1, sum(window_iters_map.values()))
                quarantined = self.drain.drained_lanes() \
                    if self.drain.enabled else set()
                for w in self.workers:
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
                    for w in self.workers
                }, iters=window_iters)
                for w in self.workers:
                    bench = (done_at[w.index] - t0) * 1000.0
                    splits = split_fence_benches(comp_at.get(w.index, ()), t0)
                    window_ms = {
                        cid: splits.get(cid, bench)
                        for cid in balanced_cids
                        # only chips that ran this id refresh its bench;
                        # split marginals when available, whole-window
                        # fence time otherwise (the documented default)
                        if self.global_ranges.get(
                            cid, [1] * len(self.workers)
                        )[w.index] > 0
                    }
                    # under the worker lock: a driver thread's end_bench
                    # holds it — an unlocked update here could be lost
                    # against (or lose) that write (ckcheck finding)
                    with w.lock:
                        w.benchmarks.update(
                            per_iteration_benches(window_ms, window_iters_map)
                        )
                # |= is a read-modify-write on the shared set; a
                # concurrent compute()'s discard must not be interleaved
                # into it (ckcheck lockset finding)
                with self._lock:
                    self._enqueue_rebalance |= balanced_cids
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
            self._enqueue_window_closed()
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
            with self._lock:
                self._enqueue_rebalance |= set(self.global_ranges.keys())

    def _enqueue_window_closed(self) -> None:
        # under the lock: compute() holds it across its check+remove on
        # the order list — an unlocked clear here could interleave
        # between those two steps and turn the remove into a ValueError
        with self._lock:
            self._enqueue_cids.clear()
            self._enqueue_cid_order.clear()
            self._enqueue_iters.clear()
            self._enqueue_t0 = None
            self._exchanging.clear()

    def ranges_of(self, compute_id: int) -> list[int]:
        return list(self.global_ranges.get(compute_id, []))

    def dispose(self) -> None:
        if self._debug_server is not None:
            self._debug_server.close()
            self._debug_server = None
        # the last chance to persist the decision tail (armed rigs only)
        DECISIONS.maybe_spill(force=True)
        for w in self.workers:
            w.dispose()
        with self._lock:
            self._owners.clear()
        self.pool.shutdown(wait=False)


def _any_partial(params: Sequence[ClArray]) -> bool:
    return any(p.flags.partial_read for p in params)
