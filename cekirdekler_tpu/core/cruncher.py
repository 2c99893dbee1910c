"""NumberCruncher — the user-facing facade over the Cores scheduler.

TPU-native analogue of the reference's ``ClNumberCruncher``
(ClNumberCruncher.cs): construct from an :class:`AcceleratorType` flag or an
explicit :class:`Devices` selection plus a kernel source (C-subset string,
``@kernel`` Python functions, or a mix); exposes the runtime toggles —
``enqueue_mode`` (:125-129), ``no_compute_mode`` (:66-70),
``performance_feed`` (:174), ``smooth_load_balancer`` (:187),
``repeat_count``/``repeat_kernel_name`` (:139-166),
``normalized_compute_powers_of_devices`` (:254-271) — and the error counter
that refuses further work after a failure (:374-392, ClArray.cs:1610-1623).
"""

from __future__ import annotations

from typing import Sequence

from ..errors import CekirdeklerError
from ..hardware import AcceleratorType, Devices, devices_for_type
from ..kernel.registry import KernelProgram, PythonKernel
from .cores import Cores

__all__ = ["NumberCruncher"]


class NumberCruncher:
    """Compile kernels for the selected chips and treat them as one device."""

    def __init__(
        self,
        devices_or_type: Devices | AcceleratorType,
        kernels: str | PythonKernel | Sequence,
        max_devices: int = 0,
    ):
        if isinstance(devices_or_type, AcceleratorType):
            devices = devices_for_type(devices_or_type, max_devices)
        else:
            devices = devices_or_type
            if max_devices > 0:
                devices = devices.subset(max_devices)
        self.number_of_errors_happened = 0
        try:
            self.program = KernelProgram(kernels)
            self.cores = Cores(devices, self.program)
        except Exception:
            self.number_of_errors_happened += 1
            raise
        self._disposed = False

    # -- device info ---------------------------------------------------------
    @property
    def devices(self) -> Devices:
        return self.cores.devices

    @property
    def num_devices(self) -> int:
        return self.cores.num_devices

    @property
    def kernel_names(self) -> list[str]:
        return self.program.kernel_names

    # -- runtime toggles (reference property parity) -------------------------
    @property
    def enqueue_mode(self) -> bool:
        return self.cores.enqueue_mode

    @enqueue_mode.setter
    def enqueue_mode(self, v: bool) -> None:
        was = self.cores.enqueue_mode
        self.cores.enqueue_mode = bool(v)
        if was and not v:
            self.cores.flush()  # leaving enqueue mode syncs results to host

    @property
    def enqueue_mode_async_enable(self) -> bool:
        """Compatibility toggle (reference: enqueueModeAsyncEnable,
        ClNumberCruncher.cs:114-118 — rotate enqueued work over 16 async
        queues).  On TPU every dispatch is already an async operation on
        the chip's stream, so this is always effectively on; the flag is
        kept for API parity and introspection."""
        return getattr(self.cores, "_async_enable", True)

    @enqueue_mode_async_enable.setter
    def enqueue_mode_async_enable(self, v: bool) -> None:
        self.cores._async_enable = bool(v)

    @property
    def last_compute_performance_report(self) -> str:
        """The most recent compute's per-device report (reference:
        lastComputePerformanceReport, ClNumberCruncher.cs:179-182)."""
        return self.cores.performance_report()

    @property
    def no_compute_mode(self) -> bool:
        return self.cores.no_compute_mode

    @no_compute_mode.setter
    def no_compute_mode(self, v: bool) -> None:
        self.cores.no_compute_mode = bool(v)

    @property
    def pipeline_lookahead(self) -> int:
        """EVENT-engine read lookahead depth (blobs staged ahead of
        compute; 1 = the reference's 3-queue wavefront)."""
        return self.cores.pipeline_lookahead

    @pipeline_lookahead.setter
    def pipeline_lookahead(self, v: int) -> None:
        self.cores.pipeline_lookahead = max(1, int(v))

    @property
    def performance_feed(self) -> bool:
        return self.cores.performance_feed

    @performance_feed.setter
    def performance_feed(self, v: bool) -> None:
        self.cores.performance_feed = bool(v)

    @property
    def fence_split(self) -> bool:
        """Per-compute-id fence splitting at enqueue-mode barriers
        (VERDICT r5 #8): marginal per-cid benches from completion-order
        probes instead of one whole-window fence time charged to every
        id in a mixed window.  Costs one extra completion wait per id per
        barrier; off by default."""
        return self.cores.fence_split

    @fence_split.setter
    def fence_split(self, v: bool) -> None:
        self.cores.fence_split = bool(v)

    @property
    def fused_dispatch(self) -> bool:
        """Fused-iteration dispatch (default True): when an enqueue
        window repeats the same compute id with unchanged partition
        ranges and HBM-resident operands, its calls defer and
        dispatch in batches as ONE dynamic-iteration-count ladder
        executable per device — collapsing the per-call dispatch floor.
        Results are bit-identical to per-iteration dispatch; disengages
        are named in ``cores.fused_stats`` and as "fused" trace
        instants (docs/PARALLELISM.md)."""
        return self.cores.fused_dispatch

    @fused_dispatch.setter
    def fused_dispatch(self, v: bool) -> None:
        if not v and self.cores.fused_dispatch:
            # an open window must not outlive the toggle
            self.cores._window.close()
        self.cores.fused_dispatch = bool(v)

    @property
    def fused_batch(self) -> int:
        """The most iterations one eager fused ladder dispatch carries
        (default 16).  A window's dispatches ramp up to it, x1 x2 x4 ..,
        so the device starts on the window's first deferred iteration
        whatever the cap; larger amortizes the dispatch floor over more
        iterations.  The executable is shared across batch sizes
        (iteration count is a runtime argument)."""
        return self.cores.fused_batch

    @fused_batch.setter
    def fused_batch(self, v: int) -> None:
        self.cores.fused_batch = max(1, int(v))

    @property
    def fused_stats(self) -> dict:
        """Fused-dispatch observability: windows dispatched, iterations
        fused/deferred, per-reason disengage counts, and how each enqueue
        window started (``window_starts``: ``ladder`` or the reason its
        first compute went per call)."""
        # ckcheck: ok racy snapshot read — reporting only
        return self.cores.fused_stats

    @property
    def streamed_transfers(self) -> bool:
        """Streamed partition transfers (default True): the plain path's
        monolithic upload → ladder → download becomes a chunked
        double-buffered read/compute/write wavefront per lane — chunk
        j+1's H2D overlaps chunk j's kernel execution, retired chunks'
        D2H overlaps later chunks' compute.  Chunk counts are autotuned
        per (lane, kernel, bytes) unless ``stream_chunks`` pins them;
        results are bit-identical to the monolithic path
        (tests/test_stream.py pins it)."""
        return self.cores.streamed_transfers

    @streamed_transfers.setter
    def streamed_transfers(self, v: bool) -> None:
        self.cores.streamed_transfers = bool(v)

    @property
    def stream_chunks(self) -> int:
        """Pinned chunk count for streamed transfers (0 = autotune via
        ``cores.transfer_tuner``, 1 = effectively monolithic)."""
        return self.cores.stream_chunks

    @stream_chunks.setter
    def stream_chunks(self, v: int) -> None:
        self.cores.stream_chunks = max(0, int(v))

    @property
    def stream_queue_depth(self) -> int:
        """Stream-driver double-buffer depth: how many chunks the host
        may stage ahead of the dispatched chunk (default 2)."""
        return self.cores.stream_queue_depth

    @stream_queue_depth.setter
    def stream_queue_depth(self, v: int) -> None:
        self.cores.stream_queue_depth = max(1, int(v))

    @property
    def transfer_tuner(self):
        """The online chunk-count autotuner (core/stream.TransferTuner):
        seed it from a duplex probe via ``seed_link`` or let streamed
        runs teach it."""
        return self.cores.transfer_tuner

    @property
    def smooth_load_balancer(self) -> bool:
        return self.cores.smooth_load_balancer

    @smooth_load_balancer.setter
    def smooth_load_balancer(self, v: bool) -> None:
        self.cores.smooth_load_balancer = bool(v)

    @property
    def adaptive_load_balancer(self) -> bool:
        """Adaptive per-chip balancer damping (default True); False =
        reference-parity fixed 0.3 damping (HelperFunctions.cs:246)."""
        return self.cores.adaptive_load_balancer

    @adaptive_load_balancer.setter
    def adaptive_load_balancer(self, v: bool) -> None:
        self.cores.adaptive_load_balancer = bool(v)

    @property
    def repeat_count(self) -> int:
        return self.cores.repeat_count

    @repeat_count.setter
    def repeat_count(self, v: int) -> None:
        self.cores.repeat_count = max(1, int(v))

    @property
    def repeat_kernel_name(self) -> str | None:
        return self.cores.repeat_sync_kernel

    @repeat_kernel_name.setter
    def repeat_kernel_name(self, name: str | None) -> None:
        self.cores.repeat_sync_kernel = name

    @property
    def normalized_compute_powers_of_devices(self) -> list[float] | None:
        return self.cores.fixed_compute_powers

    @normalized_compute_powers_of_devices.setter
    def normalized_compute_powers_of_devices(self, powers: Sequence[float] | None) -> None:
        if powers is None:
            self.cores.fixed_compute_powers = None
            return
        powers = [float(p) for p in powers]
        if len(powers) != self.num_devices:
            raise CekirdeklerError(
                f"need {self.num_devices} compute powers, got {len(powers)}"
            )
        s = sum(powers)
        self.cores.fixed_compute_powers = [p / s for p in powers]

    # -- fine-grained queue control (reference: ClNumberCruncher.cs:81-85,
    # 356-372) ---------------------------------------------------------------
    @property
    def fine_grained_queue_control(self) -> bool:
        return any(w.markers is not None for w in self.cores.workers)

    @fine_grained_queue_control.setter
    def fine_grained_queue_control(self, v: bool) -> None:
        from ..utils.markers import MarkerCounter

        for w in self.cores.workers:
            if v and w.markers is None:
                w.markers = MarkerCounter()
            elif not v and w.markers is not None:
                w.markers.close()
                w.markers = None

    def count_markers_remaining(self) -> int:
        return sum(
            w.markers.remaining() for w in self.cores.workers if w.markers is not None
        )

    def count_markers_reached(self) -> int:
        return sum(
            w.markers.reached for w in self.cores.workers if w.markers is not None
        )

    def marker_reach_speed(self) -> float:
        speeds = [
            w.markers.reach_speed() for w in self.cores.workers if w.markers is not None
        ]
        return sum(speeds)

    def performance_history(self, compute_id: int):
        return self.cores.performance_history(compute_id)

    # -- live introspection (obs/) -------------------------------------------
    def serve_debug(self, port: int = 0, host: str = "127.0.0.1"):
        """Start the debug HTTP endpoints (``/metrics``, ``/statusz``,
        ``/tracez``, ``/healthz``, ``/flightz``) on a daemon thread
        (obs/debugserver.py).  ``port=0`` = ephemeral; read
        ``server.port``.  Also auto-started by ``CK_DEBUG_PORT``."""
        return self.cores.serve_debug(port=port, host=host)

    def health_report(self) -> dict:
        """Per-lane health verdicts (obs/health.py — advisory only):
        ``{lane: {"verdict", "score", "evidence"}}``."""
        return self.cores.health_report()

    def reset_errors(self) -> None:
        """Re-arm a cruncher after a compute failure (the reference has no
        reset — a failed cruncher stays dead; we allow explicit recovery)."""
        self.number_of_errors_happened = 0

    # -- host-gated dispatch (reference: ClUserEvent.cs:29-121 +
    # Worker.cs:487-557 synchronized start) ----------------------------------
    @property
    def dispatch_gate(self):
        """A :class:`~cekirdekler_tpu.utils.events.UserEvent` (or None):
        while set and untriggered, every worker lane holds at the top of
        its compute phase; ``trigger()`` starts all lanes simultaneously.
        Call computes from a separate thread (or use enqueue mode) if the
        host must trigger after the compute call has been issued."""
        return self.cores.dispatch_gate

    @dispatch_gate.setter
    def dispatch_gate(self, gate) -> None:
        self.cores.dispatch_gate = gate

    # -- sync / reporting ----------------------------------------------------
    def flush(self) -> None:
        """Join deferred enqueue-mode work (reference:
        flushLastUsedCommandQueue, ClNumberCruncher.cs:100-106)."""
        self.cores.flush()

    def barrier(self) -> None:
        """Wait for all device work without host readback."""
        self.cores.barrier()

    def performance_report(self, compute_id: int | None = None) -> str:
        return self.cores.performance_report(compute_id)

    def benchmarks_of(self, compute_id: int) -> list[float]:
        return self.cores.benchmarks_of(compute_id)

    def ranges_of(self, compute_id: int) -> list[int]:
        return self.cores.ranges_of(compute_id)

    def dispose(self) -> None:
        if not self._disposed:
            self.cores.dispose()
            self._disposed = True

    def __enter__(self) -> "NumberCruncher":
        return self

    def __exit__(self, *exc) -> None:
        self.dispose()
