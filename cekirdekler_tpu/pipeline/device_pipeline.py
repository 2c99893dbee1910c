"""Device→device pipeline and single-chip multi-stage pipeline.

TPU-native analogue of the reference's ``ClPipeline``/``ClPipelineStage``
(ClPipeline.cs:29-2356) and ``SingleGPUPipeline.DevicePipeline``
(ClPipeline.cs:2357-3240): a linear graph of stages, each bound to a chip,
all running concurrently on successive data generations; results flow
stage→stage each ``push``.

Where the reference forwards results through HOST arrays with double
buffering (forwardResults deep-copies output→duplicate input,
ClPipeline.cs:624-1580; switchBuffers swaps the sets, :87-111), the TPU
build forwards device→device — ``jax.device_put`` moves the output value
to the next stage's chip over ICI, never touching the host.  And because
XLA arrays are immutable values, the double-buffer sets collapse to plain
value handoff: a stage's new output cannot clobber the value the next
stage still holds.

Latency: data pushed at push t is computed by stage 0 at t, reaches stage
k at push t+k; with S stages, ``push`` returns True (results valid) from
push S onward (the reference's 2·stages-2 counter covers its double-init,
ClPipeline.cs:114-122).
"""

from __future__ import annotations

import enum
import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Sequence

import jax
import numpy as np

from ..arrays.clarray import ClArray, wrap
from ..errors import CekirdeklerError, ComputeValidationError
from ..hardware import Device
from ..kernel.registry import KernelProgram
from ..trace.spans import TRACER

__all__ = ["PipelineStage", "ClPipeline", "DevicePipeline", "ArrayRole"]


class ArrayRole(enum.Enum):
    """Single-device pipeline array semantics (reference:
    DevicePipelineArrayType, ClPipeline.cs:3171-3206).

    - ``INPUT``: host-fed each feed (stage 0 of the array's stage).
    - ``OUTPUT``: host-read each feed.
    - ``INTERNAL``: persists on the device across feeds, never leaves.
    - ``TRANSITION``: written by its stage, consumed by the NEXT stage on
      the following generation (the stage→stage link).
    """

    INPUT = "input"
    OUTPUT = "output"
    INTERNAL = "internal"
    TRANSITION = "transition"


@dataclass
class _Slot:
    """A logical array bound to a stage (reference: ClPipelineStageBuffer)."""

    arr: ClArray
    role: str                      # "input" | "hidden" | "output"
    value: Any = None              # device value (jax.Array) for this stage


class PipelineStage:
    """One pipeline stage: kernels + device + input/hidden/output buffers
    (reference: ClPipelineStage, ClPipeline.cs:140-1703).

    Kernel argument order is inputs, then hiddens, then outputs.
    """

    def __init__(
        self,
        kernel_source,
        kernels: str | Sequence[str],
        global_range: int,
        local_range: int = 256,
        values: Sequence | dict = (),
        init_kernels: str | Sequence[str] = (),
        devices=None,
    ):
        self.program = KernelProgram(kernel_source)
        self.kernels = kernels.split() if isinstance(kernels, str) else list(kernels)
        self.init_kernels = (
            init_kernels.split() if isinstance(init_kernels, str) else list(init_kernels)
        )
        self.global_range = global_range
        self.local_range = local_range
        self.values = values
        self.inputs: list[_Slot] = []
        self.hiddens: list[_Slot] = []
        self.outputs: list[_Slot] = []
        self.transitions: list[_Slot] = []
        self.device: Device | None = None
        # multi-chip stage (reference: a stage owns its own cruncher over a
        # ClDevices set, ClPipeline.cs:225-285): when set, this stage runs
        # its kernels through a stage-local Cores — range load-balanced
        # across ITS devices — instead of a single-chip launcher.
        # Normalized so an empty sequence means "unassigned" everywhere
        # (make() counts and allocates on the same condition).
        self.devices = devices if devices is not None and len(devices) > 0 else None
        self._cores = None
        self.prev: "PipelineStage | None" = None
        self.next: "PipelineStage | None" = None
        self.elapsed_ms = 0.0

    # -- buffer binding (reference: addInput/Hidden/OutputBuffers) -----------
    def add_input(self, *arrays, **flags) -> "PipelineStage":
        self.inputs.extend(_Slot(wrap(a, **flags), "input") for a in arrays)
        return self

    def add_hidden(self, *arrays, **flags) -> "PipelineStage":
        self.hiddens.extend(_Slot(wrap(a, **flags), "hidden") for a in arrays)
        return self

    def add_output(self, *arrays, **flags) -> "PipelineStage":
        self.outputs.extend(_Slot(wrap(a, **flags), "output") for a in arrays)
        return self

    def add_transition(self, *arrays, **flags) -> "PipelineStage":
        """Bind TRANSITION arrays: written by this stage, consumed by the
        NEXT stage one generation later (reference:
        DevicePipelineArrayType.TRANSITION, ClPipeline.cs:3171-3206).
        The builder links the matching input slot onto the next stage."""
        self.transitions.extend(_Slot(wrap(a, **flags), "transition") for a in arrays)
        return self

    def add_array(self, arr, role: "ArrayRole", **flags) -> "PipelineStage":
        """Role-based binding (reference API shape)."""
        if role is ArrayRole.INPUT:
            return self.add_input(arr, **flags)
        if role is ArrayRole.OUTPUT:
            return self.add_output(arr, **flags)
        if role is ArrayRole.INTERNAL:
            return self.add_hidden(arr, **flags)
        return self.add_transition(arr, **flags)

    # -- graph building (reference: prependToStage/appendToStage) ------------
    def append_to(self, prev: "PipelineStage") -> "PipelineStage":
        prev.next, self.prev = self, prev
        return self

    def prepend_to(self, nxt: "PipelineStage") -> "PipelineStage":
        nxt.prev, self.next = self, nxt
        return self

    # -- execution -----------------------------------------------------------
    def _slots(self) -> list[_Slot]:
        return self.inputs + self.hiddens + self.outputs + self.transitions

    def _bind(self, jdev) -> None:
        import jax.numpy as jnp

        for s in self._slots():
            if s.value is None:
                s.value = jax.device_put(s.arr.host(), jdev)

    def _run(self, kernel_names: list[str]) -> None:
        """Launch the kernel sequence on the stage's device values."""
        import time

        if self._cores is not None:
            self._run_multi(kernel_names)
            return
        _tt = TRACER.t0("pipeline-stage")
        t0 = time.perf_counter()
        slots = self._slots()
        # placement ownership: every producer of a single-chip stage's slot
        # values (push/_bind/handoff) device_puts before we get here
        bufs = tuple(s.value for s in slots)
        offset = 0
        for name in kernel_names:
            va = (
                self.values.get(name, ())
                if isinstance(self.values, dict)
                else tuple(self.values)
            )
            fn, _ = self.program.launcher(
                name, self.global_range, self.local_range, self.global_range,
                platform=(
                    self.device.jax_device.platform
                    if self.device is not None
                    else None
                ),
            )
            n_arr = self.program.array_param_count(name)
            out = fn(offset, bufs[:n_arr], tuple(va))
            bufs = tuple(out) + bufs[n_arr:]
        for s, b in zip(slots, bufs):
            s.value = b
        self.elapsed_ms = (time.perf_counter() - t0) * 1000.0
        TRACER.record(
            "pipeline-stage", _tt,
            tag=f"{self.device.name if self.device else '?'}:"
                f"{'+'.join(kernel_names)}",
        )

    def _run_multi(self, kernel_names: list[str]) -> None:
        """Multi-chip stage body: pull incoming device values to host, run
        the kernels through the stage's own Cores (per-chip range split +
        load balancing), publish host arrays as the stage's new values —
        the reference's behavior exactly (each stage.run() is a full
        H2D/compute/D2H on that stage's devices; stage→stage data moves
        through host arrays, ClPipeline.cs:287-603,624-1580)."""
        import time

        _tt = TRACER.t0("pipeline-stage")
        t0 = time.perf_counter()
        slots = self._slots()
        for s in slots:
            if s.value is not None and not isinstance(s.value, np.ndarray):
                np.copyto(s.arr.host(), np.asarray(s.value), casting="unsafe")
                s.value = None
            elif isinstance(s.value, np.ndarray) and s.value is not s.arr.host():
                np.copyto(s.arr.host(), s.value, casting="unsafe")
                s.value = None
        params = [s.arr for s in slots]
        self._cores.compute(
            kernel_names, params, 1, self.global_range, self.local_range,
            value_args=self.values,
        )
        for s in self.outputs + self.transitions:
            s.value = s.arr.host()
        self.elapsed_ms = (time.perf_counter() - t0) * 1000.0
        TRACER.record(
            "pipeline-stage", _tt,
            tag=f"multi[{len(self.devices) if self.devices else 0}]:"
                f"{'+'.join(kernel_names)}",
        )


class ClPipeline:
    """Linear device→device pipeline (reference: ClPipeline,
    ClPipeline.cs:29-139).

    Build via ``ClPipeline.make(stages, devices)`` — one device per stage
    (reference stages may span multiple devices via their own cruncher; here
    a stage is one chip, the framework's Cores covers intra-stage
    multi-chip).
    """

    def __init__(self, stages: list[PipelineStage]):
        self.stages = stages
        self.push_count = 0
        self._pool = ThreadPoolExecutor(max_workers=max(2, len(stages)))

    @classmethod
    def make(cls, stages: Sequence[PipelineStage], devices: Sequence[Device]) -> "ClPipeline":
        """Wire a linear pipeline onto devices and run initializer kernels
        (reference: makePipeline + initializer double-run,
        ClPipeline.cs:1582-1699)."""
        stages = list(stages)
        if not stages:
            raise CekirdeklerError("pipeline needs at least one stage")
        devices = list(devices)
        unassigned = [st for st in stages if st.devices is None]
        if len(devices) == 1:
            # single-chip pipeline: every unassigned stage on the one device
            devices = devices * len(unassigned)
        if len(devices) < len(unassigned):
            raise CekirdeklerError(
                f"{len(unassigned)} stages need {len(unassigned)} devices (or "
                f"exactly 1 for a single-chip pipeline); got {len(devices)}"
            )
        dev_iter = iter(devices)
        for i, st in enumerate(stages):
            if i > 0:
                st.prev, stages[i - 1].next = stages[i - 1], st
            if st.devices is not None:
                # multi-chip stage: its own Cores over its device set
                # (reference: per-stage cruncher, ClPipeline.cs:225-285)
                from ..core.cores import Cores

                st._cores = Cores(st.devices, st.program)
                st.device = st.devices[0]
            else:
                st.device = next(dev_iter)
                st._bind(st.device.jax_device)
            for s in st._slots():
                if s.arr.size < st.global_range:
                    raise ComputeValidationError(
                        f"stage {i} array '{s.arr.name}' smaller than global range"
                    )
        # wire TRANSITION links: the producing stage's transition slot feeds
        # the slot in the NEXT stage bound to the same ClArray object
        for i, st in enumerate(stages):
            st._transition_links = []
            for t in st.transitions:
                if st.next is None:
                    raise ComputeValidationError(
                        f"stage {i} declares transition '{t.arr.name}' but has no next stage"
                    )
                target = next(
                    (s for s in st.next._slots() if s.arr is t.arr), None
                )
                if target is None:
                    raise ComputeValidationError(
                        f"transition '{t.arr.name}' of stage {i} is not bound "
                        f"on stage {i + 1} (declare it there as input/internal)"
                    )
                st._transition_links.append((t, target))
        for st in stages:
            if st.init_kernels:
                st._run(st.init_kernels)
        return cls(stages)

    def push(
        self,
        data: Sequence | None = None,
        results: Sequence | None = None,
    ) -> bool:
        """Advance the pipeline one generation (reference: pushData,
        ClPipeline.cs:49-122).

        ``data``: host arrays for stage 0's inputs (optional).
        ``results``: host arrays that receive the LAST stage's outputs
        (optional).  Returns True once results are valid (push_count ≥
        number of stages).
        """
        first, last = self.stages[0], self.stages[-1]
        if data is not None:
            datas = list(data) if isinstance(data, (list, tuple)) else [data]
            if len(datas) != len(first.inputs):
                raise ComputeValidationError(
                    f"push data count {len(datas)} != stage-0 inputs {len(first.inputs)}"
                )
            for slot, d in zip(first.inputs, datas):
                host = d.host() if isinstance(d, ClArray) else np.asarray(d)
                if first._cores is not None:
                    # multi-chip stage consumes host data directly
                    np.copyto(slot.arr.host(), host, casting="unsafe")
                    slot.value = None
                else:
                    slot.value = jax.device_put(host, first.device.jax_device)

        # all stages compute concurrently on their current values
        futures = [self._pool.submit(st._run, st.kernels) for st in self.stages]
        errs = []
        for f in futures:
            try:
                f.result()
            except Exception as e:  # noqa: BLE001 - first error surfaces
                errs.append(e)
        if errs:
            # black box before the raise (obs/flight.py): a crashed
            # pipeline generation dumps the flight/span/metrics state
            # when CK_POSTMORTEM_DIR is armed
            from ..obs.flight import record_crash

            record_crash("pipeline.push", errs[0], lanes={
                "stages": len(self.stages),
                "push_count": self.push_count,
            })
            raise errs[0]

        # read back last stage's outputs (device→host)
        if results is not None:
            outs = list(results) if isinstance(results, (list, tuple)) else [results]
            if len(outs) != len(last.outputs):
                raise ComputeValidationError(
                    f"results count {len(outs)} != last-stage outputs {len(last.outputs)}"
                )
            for slot, r in zip(last.outputs, outs):
                target = r.host() if isinstance(r, ClArray) else r
                np.copyto(target, np.asarray(slot.value), casting="unsafe")

        self._switch()
        self.push_count += 1
        return self.push_count >= len(self.stages)

    def _switch(self) -> None:
        """Advance generation links (the reference's switchBuffers +
        forwardResults, ClPipeline.cs:87-111,624-1580): explicit TRANSITION
        links move first; stages without transitions fall back to by-index
        output→input forwarding.  Same-chip handoff is a free value move;
        cross-chip rides ICI via ``device_put``."""
        def handoff(v, nxt):
            # a multi-chip producer publishes its LIVE arr.host() buffer,
            # which its own next-generation compute overwrites concurrently
            # with the consumer's read — and jax.device_put of a numpy
            # array may read it lazily, racing the same way.  Snapshot
            # host-published values for EVERY consumer kind.
            if isinstance(v, np.ndarray):
                v = np.array(v)
            if nxt._cores is not None:
                # multi-chip consumer takes host data (its compute uploads
                # per-chip range slices from it)
                return v if isinstance(v, np.ndarray) else np.asarray(v)
            return jax.device_put(v, nxt.device.jax_device)

        for st in self.stages[:-1]:
            nxt = st.next
            links = getattr(st, "_transition_links", [])
            if links:
                for src, dst in links:
                    dst.value = handoff(src.value, nxt)
                continue
            n = min(len(st.outputs), len(nxt.inputs))
            for o_slot, i_slot in zip(st.outputs[:n], nxt.inputs[:n]):
                i_slot.value = handoff(o_slot.value, nxt)

    @property
    def streamed_transfers(self) -> bool:
        """Streamed partition transfers inside multi-chip stages: each
        such stage runs its kernels through a stage-local ``Cores``,
        which chunk-streams its per-lane H2D/D2H exactly like the main
        scheduler (core/phase.Phases._streamed) — stage feeds stop paying
        the monolithic upload-before-first-launch fence.  True iff every
        multi-chip stage has it on (single-chip stages keep values
        device-resident and have no partition transfers to stream)."""
        cores = [st._cores for st in self.stages if st._cores is not None]
        return bool(cores) and all(c.streamed_transfers for c in cores)

    @streamed_transfers.setter
    def streamed_transfers(self, v: bool) -> None:
        for st in self.stages:
            if st._cores is not None:
                st._cores.streamed_transfers = bool(v)

    @property
    def stream_chunks(self) -> int:
        """Pinned chunk count for the stage-local schedulers (0 =
        autotune; the per-stage ``Cores.transfer_tuner`` learns each
        stage's own (lane, kernel, bytes) points independently)."""
        for st in self.stages:
            if st._cores is not None:
                return st._cores.stream_chunks
        return 0

    @stream_chunks.setter
    def stream_chunks(self, v: int) -> None:
        for st in self.stages:
            if st._cores is not None:
                st._cores.stream_chunks = max(0, int(v))

    def performance_report(self) -> str:
        lines = ["pipeline stages:"]
        for i, st in enumerate(self.stages):
            lines.append(
                f"  stage {i} [{st.device.name if st.device else '?'}]: "
                f"{st.elapsed_ms:8.3f} ms  kernels={' '.join(st.kernels)}"
            )
        return "\n".join(lines)

    def dispose(self) -> None:
        self._pool.shutdown(wait=False)
        for st in self.stages:
            if st._cores is not None:
                st._cores.dispose()
                st._cores = None
            for s in st._slots():
                s.value = None


class DevicePipeline(ClPipeline):
    """Single-chip N-stage pipeline (reference: SingleGPUPipeline.
    DevicePipeline, ClPipeline.cs:2357-3240) — same generation semantics,
    every stage on ONE chip; device-side concurrency comes from XLA async
    dispatch (replacing the reference's enqueue-mode queue rotation), and
    HOST-side overlap comes from the ``feed_async_begin``/``feed_async_end``
    pair: the device generation runs on a background thread while the
    caller prepares the next feed's data (reference: feedAsync /
    feedAsyncBegin/End, ClPipeline.cs:2598-2641).

    Array roles (:class:`ArrayRole`) map the reference's
    DevicePipelineArrayType semantics (ClPipeline.cs:3171-3206): INPUT is
    host-fed, OUTPUT host-read, INTERNAL device-resident, TRANSITION
    carries data stage→stage one generation later.
    """

    def __init__(self, stages: list[PipelineStage]):
        super().__init__(stages)
        self._async_future = None

    @classmethod
    def make(cls, stages: Sequence[PipelineStage], device: Device) -> "DevicePipeline":
        return super().make(stages, [device])

    def feed(self, data=None, results=None) -> bool:
        """Synchronous generation (reference: feed, ClPipeline.cs:2577-2593)."""
        return self.push(data, results)

    # -- async host-overlap feeds (reference: ClPipeline.cs:2598-2641) -------
    def _generation(self, snaps) -> None:
        """One device generation: upload snapshots, run every stage, switch
        links.  Runs on a background thread for the async feeds."""
        first = self.stages[0]
        if snaps is not None:
            for slot, host in zip(first.inputs, snaps):
                slot.value = jax.device_put(host, first.device.jax_device)
        for st in self.stages:
            st._run(st.kernels)
        self._switch()

    def feed_async_begin(self, data=None) -> None:
        """Kick off this generation on a background thread and return
        immediately — the host thread is free to prepare the next feed
        (the overlap the reference gets from async enqueue + Parallel.For
        host copies).  Input data is snapshotted NOW, so the caller may
        mutate its arrays right after this returns."""
        if self._async_future is not None:
            raise CekirdeklerError(
                "feed_async_begin called again before feed_async_end"
            )
        snaps = None
        if data is not None:
            datas = list(data) if isinstance(data, (list, tuple)) else [data]
            if len(datas) != len(self.stages[0].inputs):
                raise ComputeValidationError(
                    f"push data count {len(datas)} != stage-0 inputs "
                    f"{len(self.stages[0].inputs)}"
                )
            snaps = [
                np.array(d.host() if isinstance(d, ClArray) else d)
                for d in datas
            ]
        self._async_future = self._pool.submit(self._generation, snaps)

    def feed_async_end(self, results=None) -> bool:
        """Join the in-flight generation and read back the last stage's
        outputs.  Returns True once results are valid."""
        if self._async_future is None:
            raise CekirdeklerError("feed_async_end without feed_async_begin")
        fut, self._async_future = self._async_future, None
        fut.result()
        if results is not None:
            last = self.stages[-1]
            outs = list(results) if isinstance(results, (list, tuple)) else [results]
            if len(outs) != len(last.outputs):
                raise ComputeValidationError(
                    f"results count {len(outs)} != last-stage outputs {len(last.outputs)}"
                )
            for slot, r in zip(last.outputs, outs):
                target = r.host() if isinstance(r, ClArray) else r
                np.copyto(target, np.asarray(slot.value), casting="unsafe")
        self.push_count += 1
        return self.push_count >= len(self.stages)

    def feed_async(self, data=None, results=None) -> bool:
        """begin + end composed (reference: feedAsync)."""
        self.feed_async_begin(data)
        return self.feed_async_end(results)
