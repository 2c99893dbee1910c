"""Cores — the multi-chip scheduler: split / compute / join with iterative
load balancing.

TPU-native analogue of the reference's ``Cores`` (Cores.cs): owns one
:class:`Worker` per chip (Cores.cs:56,260-262), the per-compute-id
``global_ranges``/``global_references`` tables (Cores.cs:130-135), and the
``compute()`` orchestration entry (Cores.cs:471-963) — first call splits the
global range equally (Cores.cs:569-596), every later call re-partitions from
measured per-chip times via :func:`core.balance.load_balance`
(HelperFunctions.cs:190-280 port), then hands every chip its phase
concurrently (the reference's ``Parallel.For`` phases, Cores.cs:746-835,
become a thread pool over async XLA dispatch).

``Cores`` is the COORDINATOR; four collaborators own the rest, each its
own state, and none of them imports this module::

    cruncher -> cores -> window    the enqueue window: its ledger, fused
                                   dispatch, how a compute goes (route)
                      -> exchange  who holds which elements; the strips
                      -> phase     one lane's phase: the four engines
                                   and the rule for which bytes cross
                      -> sync      flush, the range move's resync, barrier
                                -> worker -> kernel/registry
"""

from __future__ import annotations

import os
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field, fields
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
from ..kernel.registry import KernelProgram
from ..metrics.registry import REGISTRY
from ..obs.debugserver import DEBUG_PORT_ENV
from ..obs.decisions import DECISIONS
from ..obs.drain import DrainController, apply_quarantine
from ..obs.flight import FLIGHT, record_crash
from ..obs.health import HealthMonitor
from ..trace.spans import TRACER
from ..utils.faultinject import FAULTS
from . import compilecache
from .balance import (
    BalanceHistory,
    BalanceState,
    equal_split,
    load_balance,
    prior_split,
)
from .compilecache import CACHE as COMPILE_CACHE
from .compilecache import probe_counts, trim_placed_jax_cache
from .exchange import Exchange
from .phase import PIPELINE_DRIVER, PIPELINE_EVENT, Job, Phases
from .sync import Sync
from .window import DEFERRED, Window, job_signature, write_all_owners
from .worker import Worker

__all__ = ["Cores", "PIPELINE_EVENT", "PIPELINE_DRIVER", "ComputePerf",
           "job_signature"]


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
class Settings:
    """The runtime toggles more than one part of the scheduler reads, in
    ONE object shared by reference: ``Cores``' attributes of these names
    read and write it (``NumberCruncher``'s properties, which document
    them, go through those)."""

    enqueue_mode: bool = False
    fused_dispatch: bool = True
    fused_batch: int = 16       # the ramp's cap
    fused_queue_depth: int = 2  # the per-device host dispatch backlog
    repeat_count: int = 1
    repeat_sync_kernel: str | None = None
    no_compute_mode: bool = False  # I/O only (reference: noComputeMode)
    streamed_transfers: bool = True
    stream_chunks: int = 0       # 0 = autotune, n = pin
    stream_queue_depth: int = 2  # the double buffer
    pipeline_lookahead: int = 2  # the EVENT engine's (phase.Phases._event)
    # host-gated dispatch (reference: ClUserEvent bound to queues +
    # Worker.cs:487-557 synchronized start): when set, every worker
    # lane blocks on the event before its compute phase
    dispatch_gate: Any = None


class _Shared:
    """A ``Cores`` attribute that lives in the scheduler's
    :class:`Settings`: the same name, read and written there."""

    def __init__(self, name: str):
        self.name = name

    def __get__(self, obj, owner=None):
        return self if obj is None else getattr(obj._settings, self.name)

    def __set__(self, obj, value) -> None:
        setattr(obj._settings, self.name, value)


def _check_vector_params(kernel: str, widths: tuple, params) -> None:
    """A ``__global floatN*`` parameter binds an array of the element type
    with ``N`` elements a vector: the array is whole vectors, and where a
    transfer is cut by the work-item range (``partial_read``, a write-back
    that is not ``write_all``) a work item's share, ``elements_per_work_item``
    elements, is whole vectors too (upstream's ``numberOfElementsPerWorkItem``:
    4 for a ``float4`` an item)."""
    for pos, (n, p) in enumerate(zip(widths, params)):
        if not n:
            continue
        if p.size % n:
            raise ComputeValidationError(
                f"vector-array-length: kernel {kernel!r} takes array "
                f"'{p.name}' (parameter {pos}) as vectors of {n}, and its "
                f"{p.size} elements are no whole number of them")
        f = p.flags
        ranged = f.partial_read or (
            f.write and not f.write_all and not f.read_only)
        if ranged and f.elements_per_work_item % n:
            raise ComputeValidationError(
                f"vector-elements-per-work-item: kernel {kernel!r} takes "
                f"array '{p.name}' (parameter {pos}) as vectors of {n}, but "
                f"its ranged transfer moves elements_per_work_item = "
                f"{f.elements_per_work_item} elements a work item, which "
                f"would cut a vector; set it to {n} (or a multiple)")


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
        self._settings = Settings()
        self.workers = [Worker(d.jax_device, i) for i, d in enumerate(devices)]
        # heterogeneous lanes: each lane's device KIND and its
        # table-derived relative-rate prior (hardware.rate_prior).  A
        # mixed TPU + host-CPU fleet seeds its FIRST split from these
        # priors (prior_split in _ranges_for) instead of the equal
        # split, so the 10-100x-slower host lane starts near its
        # rate-implied share and the measured balancer only has to trim
        # — not rescue — the partition.  Both are plain attributes:
        # tools emulating a mixed fleet on virtual lanes (hetero_sweep,
        # resilience scenarios) overwrite rate_priors the same way they
        # pin fixed_compute_powers.
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
        self.last_compute_id: int | None = None
        # kernel-verify advisory dedupe, keyed on (kernel sequence,
        # first finding fingerprint) — NOT object identity: the
        # program's verdict cache is written lock-free, so a racing
        # first-verify can hand this method a verdict the cache then
        # drops, and a recycled id() would suppress a different
        # shape's one-and-only advisory forever
        self._verify_notified: set[tuple] = set()
        # per-cid fence splitting (barrier's docstring): off by default —
        # it costs one extra completion wait per cid in the window (plus
        # workers pinning the probe buffers), and homogeneous windows are
        # measured exactly either way
        self._fence_split = False
        # lane health scoring (obs/health.py): rolling per-lane baselines
        # over fence walls, transfer walls, and stream-driver stalls,
        # fed at sync points / phase tails (never the deferral hot path);
        # health_report() / /healthz read the verdicts
        self._health = HealthMonitor()
        # drain ACTUATOR (obs/drain.py): consumes the monitor's
        # verdicts at every barrier — a degraded lane is quarantined
        # (share masked to 0 via apply_quarantine in _ranges_for, the
        # displaced share redistributed onto surviving lanes), probed
        # after a hold, and re-admitted with hysteresis when the
        # verdict clears
        self._drain = DrainController(self._health, lanes=len(self.workers))
        # the collaborators (module docstring).  ``_lock`` is the
        # SCHEDULER lock, the window's: core/window.py has the table of
        # what it guards.  ``fused_stats``, ``transfer_tuner`` and
        # ``last_stream_chunks`` are their owners' own objects.
        owners: dict[int, tuple] = {}
        self._window = Window(
            self._settings, program, self.workers, self.global_ranges,
            self.global_references, self.drain, owners, self._record_perf,
            self._lane_config)
        self._lock = self._window.lock
        self.fused_stats = self._window.stats
        self._exchange = Exchange(
            program, self.workers, self.pool, self._lock, owners)
        self._phase = Phases(
            self._settings, program, self._window, self.health,
            single=len(self.workers) == 1)
        self.transfer_tuner = self._phase.transfer_tuner
        self.last_stream_chunks = self._phase.last_stream_chunks
        self._sync = Sync(
            self._settings, self.workers, self.pool, self._window,
            self.health, self.drain, self.global_ranges, self._lane_config)
        # cached handle — no registry get-or-create on the compute path
        self._m_stream_retunes = REGISTRY.counter(
            "ck_stream_retune_total",
            "transfer-autotuner re-tunes forced by re-partitions")
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

    # the two observers are handed to the collaborators that feed and ask
    # them; a rig that replaces one (tools/resilience.py) replaces it there
    @property
    def health(self) -> HealthMonitor:
        return self._health

    @health.setter
    def health(self, monitor: HealthMonitor) -> None:
        self._health = self._phase.health = self._sync.health = monitor

    @property
    def drain(self) -> DrainController:
        return self._drain

    @drain.setter
    def drain(self, controller: DrainController) -> None:
        self._drain = self._window.drain = self._sync.drain = controller

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
            widths = self.program.vector_widths(name)
            if widths:
                _check_vector_params(name, widths, params)
        step = local_range * (pipeline_blobs if pipeline else 1)
        if global_range % step != 0:
            raise ComputeValidationError(
                f"global_range ({global_range}) must be divisible by step ({step})"
            )
        if global_offset % local_range and any(
                self.program.cooperates(n) for n in kernel_names):
            raise ComputeValidationError(
                f"global_offset ({global_offset}) must be a multiple of the "
                f"local range ({local_range}) for a kernel with a __local "
                "array or a barrier: a launch covers whole work-groups")
        t_start = time.perf_counter()
        s = self._settings
        win = self._window
        # the call's "enqueue" span (ring and, in a profiler session, the
        # ck/enqueue annotation): closed by Window.defer for a deferred
        # call and before the engage walk for a per-call one.  The first
        # call of an enqueue window, and every non-windowed call, opens
        # the next ``win``: the identifier its spans share across threads.
        # ``opens``: this call opens an enqueue window (the first since a
        # barrier); ``how`` it went per call (the reason) is counted and
        # rides its span
        _tt = TRACER.t0("enqueue")
        opens = s.enqueue_mode and win.t0 is None
        if _tt and (opens or not s.enqueue_mode):
            TRACER.next_window()
        # ROUTE: deferred into a fused window (the call is a counter
        # increment, and nothing below runs), or per call
        sig = job_signature(
            kernel_names, params, compute_id, global_range, local_range,
            global_offset, value_args) if s.enqueue_mode and not pipeline \
            else None
        how = win.route(sig, kernel_names, compute_id, global_offset,
                        pipeline, t_start, _tt, opens)
        if how is DEFERRED:
            if self.performance_feed:
                # the feed wants a printed row per call — keep the full
                # record on that (diagnostic) configuration only
                self._record_perf(compute_id, t_start,
                                  self.global_ranges.get(compute_id, []))
            else:
                self.last_compute_id = compute_id
            return
        # VERDICT: kernel partition-safety / flag-soundness gate
        # (analysis/, docs/STATIC_ANALYSIS.md "Kernel partition-safety"):
        # verdicts cache per launch shape in the program, so steady state
        # pays one env read + one dict hit.  Deferred calls never reach
        # this point — the window's engage call already verified the
        # identical shape.  Advisory by default (one flight event per
        # shape); CK_KERNEL_VERIFY=strict raises the named finding.
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
                window=s.enqueue_mode or s.repeat_count > 1,
                exchange=not pipeline and s.repeat_count == 1
                and not s.repeat_sync_kernel,
                lanes=self.num_devices,
            )
            if verdict.errors:
                if verify_mode == "strict":
                    raise KernelVerifyError(verdict.errors[0])
                self._note_kernel_verdict(verdict, kernel_names)
            elif verdict.reach and self.num_devices > 1:
                reach = self._exchange.reach_elements(
                    verdict, kernel_names, value_args)
                # a synchronous compute whose reads are all full and that
                # stores to nothing it reads across lanes takes the whole
                # arrays from the host, as ever
                if reach and (s.enqueue_mode or any(
                        params[pos].flags.partial_read
                        or pos in verdict.writes for pos in reach)):
                    exchange = (verdict, reach)
        if opens and how is not None:
            if exchange is not None:
                how = "halo"
            win.note_start(how)
        if s.enqueue_mode:
            win.note_call(compute_id, t_start)
        # RANGES.  Enqueue mode cannot rebalance on per-call host benches
        # (they only measure async dispatch time), so ranges hold still
        # BETWEEN syncs and move AT them: barrier() arms a one-shot
        # rebalance for the next call.  Residency stays correct across a
        # move because workers skip re-uploads only for covered ranges.
        old_ranges = list(self.global_ranges.get(compute_id, ()))
        _ts = TRACER.t0("schedule")
        ranges, refs = self._ranges_for(
            compute_id,
            global_range,
            step,
            rebalance=(not s.enqueue_mode) or compute_id in win.rebalance,
        )
        TRACER.record("schedule", _ts, cid=compute_id)
        win.disarm(compute_id)
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
        if s.enqueue_mode and old_ranges and ranges != old_ranges \
                and exchange is None:
            # (a compute that reads across lanes fetches a gained strip
            # from the lane that held it, as it fetches its reach: below)
            # the balancer moved shares between syncs: host arrays must be
            # made current BEFORE any chip uploads its newly-acquired region
            # (the freshest data for that region is on the previous owner's
            # HBM; its deferred download record is pending) — and every
            # chip's upload coverage is reset, else a chip RE-acquiring a
            # range it held before an earlier move would pass
            # upload_covers() on stale coverage.  ONE atomic step
            # (Sync.flush_and_reset_coverage says why).
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
        # thread's end_bench / a concurrent flush's transfer feed
        for i, w in enumerate(self.workers):
            if ranges[i] > 0:
                continue
            with w.lock:
                if w.benchmarks.get(compute_id, 0.0) > 0.0:
                    w.benchmarks[compute_id] *= 0.5
                if w.transfer_benchmarks.get(compute_id, 0.0) > 0.0:
                    w.transfer_benchmarks[compute_id] *= 0.5

        active = [i for i in range(self.num_devices) if ranges[i] > 0]
        job = Job(kernel_names, params, compute_id, local_range,
                  global_range, pipeline, pipeline_blobs, pipeline_type,
                  value_args, write_all_owners(params, active))
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
        futures = [
            self.pool.submit(
                TRACER.bind(self._run_worker, i), self.workers[i], job,
                global_offset + refs[i], ranges[i], plans.get(i))
            for i in active]
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
        # the map on its own thread; the writes hold the lock
        if exchange is not None or self._exchange.owners:
            self._exchange.note_writers(
                exchange, params, global_offset, ranges, refs)
        if _tt:
            TRACER.record(
                "enqueue", _tt, cid=compute_id, tag="+".join(kernel_names),
                **({"start": "per-call:" + how} if opens and how else {}),
            )
        self._record_perf(compute_id, t_start, ranges)
        if exchange is not None and s.enqueue_mode:
            # no fusion across an exchange: the computes of such a window
            # cannot be deferred into one device-side loop a lane, each is
            # its own launch with the fetch before it.  Said once a window.
            if not win.note_exchanging(compute_id) and s.fused_dispatch:
                win.note_disengage("halo", compute_id)
            return
        # fused-window engagement: a successfully dispatched enqueue call
        # whose next identical call would be a pure launch (operands
        # resident, ranges pinned) establishes the window this call's
        # geometry defines — subsequent matching calls defer
        if s.enqueue_mode and s.fused_dispatch and not pipeline:
            _te = TRACER.t0("engage")
            win.try_engage(
                sig, kernel_names, params, compute_id, global_range,
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

    # -- the thin entries the collaborators are reached through -------------
    # (a patch on the class must bite: the benchmark's checks and the tests
    # replace these three)
    def _stage_exchange(self, exchange, params, compute_id: int,
                        global_offset: int, ranges, refs, step: int) -> dict:
        """Before the lanes of a compute that reads across lanes launch:
        one plan a lane of what must be made current there
        (``Exchange.stage``)."""
        return self._exchange.stage(
            exchange, params, global_offset, ranges, refs, step,
            windowed=self._settings.enqueue_mode)

    def _start_deferred_downloads(self, pending, lock_each: bool) -> list:
        """The issue of the deferred readbacks
        (``Sync.start_deferred_downloads``)."""
        return self._sync.start_deferred_downloads(pending, lock_each)

    def _flush_and_reset_coverage(self) -> None:
        """The resync after a range move: flush and coverage reset as ONE
        atomic step (``Sync.flush_and_reset_coverage``)."""
        self._sync.flush_and_reset_coverage(self._start_deferred_downloads)

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

    # -- externally-assembled batches (the serving tier's entry) -------------
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
        ``Window._dispatch`` — so re-dispatching it is bit-exact).  The
        serving tier's blast-radius containment
        (``serve/resilience.py``) is the consumer."""
        iters = int(iters)
        if iters < 1:
            raise ComputeValidationError(
                f"compute_fused_batch needs iters >= 1, got {iters}")
        if not self._settings.enqueue_mode:
            raise ComputeValidationError(
                "compute_fused_batch requires enqueue_mode (deferred "
                "readbacks are the batch contract)")
        win = self._window
        sig = job_signature(
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
            hits0, misses0 = probe_counts()
        done = 0
        ladder = 0
        try:
            while done < iters:
                t_start = time.perf_counter()
                # ckcheck: ok racy reads — single enqueue driver
                opens = win.t0 is None
                deferred = win.defer_many(sig, iters - done, t_start)
                if not deferred and win.sig is None:
                    # no window open: one that repeats the last starts
                    # on the ladder, the whole batch in it
                    deferred = (
                        win.start(sig, compute_id, global_offset) is None
                        and win.defer_many(sig, iters - done, t_start))
                    if deferred and opens:
                        win.note_start("ladder")
                if deferred:
                    ladder = iters - done
                    done = iters
                    break
                # lane preflight BEFORE the per-call dispatch: an armed
                # driver-submit clause (fused or stream queue) fires
                # here, while nothing of this iteration has reached any
                # lane — a CLEAN failure containment can re-dispatch.
                # The iteration's own stream submits then skip their
                # fire (batch_preflighted): a mid-phase fire after
                # some lanes launched would be dirty by construction.
                if FAULTS.enabled:
                    # the worker preflight stamps _ck_clean_window per
                    # raise source (fault = clean, popped prior error
                    # = NOT clean — see _DriverQueue.preflight)
                    for w in self.workers:
                        w.stream_preflight()
                win.batch_preflighted = True
                try:
                    self.compute(
                        kernel_names, params, compute_id, global_range,
                        local_range, global_offset=global_offset,
                        value_args=value_args,
                    )
                finally:
                    win.batch_preflighted = False
                done += 1
        except Exception as e:
            # surface the per-window failure cause as STRUCTURE, not one
            # opaque sync-point exception (the serving tier's blast-
            # radius containment input, serve/resilience.py):
            # applied_iters = iterations that completed dispatch before
            # the failure, clean = the failed residue was never queued
            # to any lane (the dispatch preflight raised — see
            # Window._dispatch), so re-dispatching it is bit-exact.  A
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
            hits1, misses1 = probe_counts()
            out["cache_hits"] = hits1 - hits0
            out["cache_misses"] = misses1 - misses0
        return out

    def warmup(self, plan) -> dict:
        """AOT-precompile a workload plan's launch ladders BEFORE traffic
        arrives (``compilecache.warmup`` has the contract)."""
        return compilecache.warmup(self.program, self.workers, plan)

    # -- per-worker phase (reference: Cores.cs:746-835 / 1197-1980) ----------
    def _run_worker(self, w: Worker, job: Job, offset: int, size: int,
                    plan=None) -> None:
        gate = self._settings.dispatch_gate
        if gate is not None:
            # ckcheck: ok user-triggered gate — blocking until the
            # caller fires it IS the ClUserEvent synchronized-start
            # semantic (reference: Worker.cs:487-557)
            gate.wait()
        # where the lane's phase begins and where it has its lane (trace/
        # spans.py, "Part marks"): the caller's ``part:submit`` to
        # ``phase-start`` is the pool hop (``hop_us``: the closure's own
        # count of it), ``phase-start`` to ``phase-locked`` the wait for
        # the lane, and from there to the lane's first upload or launch
        # ``classify``, the tuner's ``choose`` and ``ensure_resident``
        _on = TRACER.active()
        if _on:
            TRACER.instant("enqueue", cid=job.compute_id, lane=w.index,
                           tag="phase-start", **TRACER.hop_meta())
        # serialize whole phases per worker: concurrent host threads driving
        # DIFFERENT compute ids through one Cores (the reference's
        # kernelWithId concurrency contract, Worker.cs:291-316) otherwise
        # interleave read-modify-write on the worker's buffer/coverage
        # dicts.  The bench starts after acquisition so one id's measured
        # time never includes waiting on another id's phase.
        with w.lock:
            if _on:
                TRACER.instant("enqueue", cid=job.compute_id, lane=w.index,
                               tag="phase-locked")
            self._phase.run(w, job, offset, size, plan)
        # from here on a caller still inside its join waits for another lane
        TRACER.instant("enqueue", cid=job.compute_id, lane=w.index,
                       tag="phase-done")

    # -- enqueue-mode sync (reference: flushLastUsedCommandQueue / finish) ----
    def flush(self) -> None:
        """Read back and join everything deferred by enqueue mode.  Any
        open fused window is dispatched and drained first — the download
        slices must see the post-ladder buffers."""
        self._sync.flush(self._start_deferred_downloads)

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
        self._sync.barrier(self._fence_split)

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
        self._exchange.clear()
        self.pool.shutdown(wait=False)


for _f in fields(Settings):
    setattr(Cores, _f.name, _Shared(_f.name))
