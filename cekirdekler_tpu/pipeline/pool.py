"""Task pool + device pool batch scheduler.

TPU-native analogue of the reference's ``Pool.*`` namespace
(ClPipeline.cs:3241-5080): freeze compute calls into :class:`ClTask`
objects, queue them in :class:`ClTaskPool`, and let a
:class:`ClDevicePool` drain pools greedily — each chip runs its own
consumer thread with a private per-chip scheduler, taking the next task
the moment it goes idle (the reference's DEVICE_COMPUTE_AT_WILL,
ClPipeline.cs:3792-3807).

Control tasks mirror the reference's private message protocol
(ClPipeline.cs:3247-3321):

- ``DEVICE_SELECT_BEGIN(i)`` / ``DEVICE_SELECT_END`` — pin the tasks in
  between to chip ``i``.
- ``GLOBAL_SYNCHRONIZATION`` — barrier: everything dispatched before it
  completes before anything after it starts.
- ``BROADCAST`` — run the task once on EVERY chip (replicated init).
- ``SERIAL_MODE_BEGIN`` / ``SERIAL_MODE_END`` — strict submission-order
  execution (a barrier after every task in the span).

Chips can be hot-added mid-run (reference: addDevice spawns a new
DevicePoolThread live, ClPipeline.cs:4333-4390).
"""

from __future__ import annotations

import enum
import itertools
import queue
import threading
import time
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Sequence

from ..arrays.clarray import ClArray, ParameterGroup
from ..core.cruncher import NumberCruncher
from ..errors import CekirdeklerError
from ..hardware import Device, Devices
from ..metrics.registry import REGISTRY
from ..trace.spans import TRACER

__all__ = ["ClTaskType", "ClTask", "ClTaskPool", "ClDevicePool", "PoolType"]

_task_ids = itertools.count(1)


class ClTaskType(enum.Enum):
    COMPUTE = "compute"
    DEVICE_SELECT_BEGIN = "device_select_begin"
    DEVICE_SELECT_END = "device_select_end"
    GLOBAL_SYNCHRONIZATION = "global_synchronization"
    BROADCAST = "broadcast"
    SERIAL_MODE_BEGIN = "serial_mode_begin"
    SERIAL_MODE_END = "serial_mode_end"


class PoolType(enum.Enum):
    DEVICE_COMPUTE_AT_WILL = "at_will"   # greedy (reference default)
    # DEVICE_ROUND_ROBIN exists in the reference but is unimplemented there
    # (ClPipeline.cs:3792-3807); we reserve the name for parity
    DEVICE_ROUND_ROBIN = "round_robin"


@dataclass
class ClTask:
    """A frozen compute call (reference: ClTask, ClPipeline.cs:3331-3520).

    Built via ``array.task(...)`` / ``group.task(...)`` (ClArray.cs:1552)
    or directly.  ``callback`` fires after completion with the task.
    """

    params: Sequence[ClArray] = ()
    kernel_names: Sequence[str] = ()
    compute_id: int = 0
    global_range: int = 0
    local_range: int = 256
    global_offset: int = 0
    values: Sequence | dict = ()
    task_type: ClTaskType = ClTaskType.COMPUTE
    select_device: int | None = None       # DEVICE_SELECT_BEGIN argument
    callback: Callable[["ClTask"], None] | None = None
    # tenant tag: the serving tier's per-tenant label (serve/), carried
    # so pool tasks attribute to the same tenant series (None = the
    # untagged pre-serving behavior, metrics unchanged)
    tenant: str | None = None
    task_id: int = field(default_factory=lambda: next(_task_ids))

    def compute(self, cruncher: NumberCruncher) -> None:
        """Run the frozen call on the given cruncher (reference:
        ClTask.compute, ClPipeline.cs:3386)."""
        group = ParameterGroup(list(self.params))
        group.compute(
            cruncher,
            self.compute_id,
            list(self.kernel_names),
            self.global_range,
            self.local_range,
            global_offset=self.global_offset,
            values=self.values,
        )

    @staticmethod
    def device_select_begin(device_index: int) -> "ClTask":
        return ClTask(task_type=ClTaskType.DEVICE_SELECT_BEGIN, select_device=device_index)

    @staticmethod
    def device_select_end() -> "ClTask":
        return ClTask(task_type=ClTaskType.DEVICE_SELECT_END)

    @staticmethod
    def global_synchronization() -> "ClTask":
        return ClTask(task_type=ClTaskType.GLOBAL_SYNCHRONIZATION)

    @staticmethod
    def serial_mode_begin() -> "ClTask":
        return ClTask(task_type=ClTaskType.SERIAL_MODE_BEGIN)

    @staticmethod
    def serial_mode_end() -> "ClTask":
        return ClTask(task_type=ClTaskType.SERIAL_MODE_END)

    def as_broadcast(self) -> "ClTask":
        """Mark this task to run once on every chip (reference BROADCAST)."""
        self.task_type = ClTaskType.BROADCAST
        return self


class ClTaskPool:
    """Thread-safe ordered task list (reference: ClTaskPool,
    ClPipeline.cs:3650-3790)."""

    def __init__(self, tasks: Sequence[ClTask] = ()):  # noqa: D107
        self._tasks: list[ClTask] = list(tasks)
        self._lock = threading.Lock()

    def add(self, task: ClTask) -> "ClTaskPool":
        with self._lock:
            self._tasks.append(task)
        return self

    def feed(self, other: "ClTaskPool", tenant: str | None = None) -> None:
        """Append copies of another pool's tasks (reference: feed,
        ClPipeline.cs:3660-3670).

        ``tenant`` tags the fed tasks with the serving tier's per-tenant
        label (``ClTask.tenant``) so pool work attributes to the same
        ``tenant=...`` metric series the front-end uses; tasks already
        carrying their own tag keep it, and untagged feeds (the default)
        change nothing.

        ``other.snapshot()`` is taken BEFORE acquiring our lock: holding
        it across the call nests two ClTaskPool locks, so concurrent
        ``a.feed(b)`` / ``b.feed(a)`` acquire them in opposite orders —
        the ABBA deadlock ckcheck's lock-order pass flags (and
        ``a.feed(a)`` would self-deadlock on the non-reentrant lock)."""
        tasks = other.snapshot()
        if tenant is not None:
            tasks = [
                t if t.tenant is not None else replace(t, tenant=str(tenant))
                for t in tasks
            ]
        with self._lock:
            self._tasks.extend(tasks)

    def snapshot(self) -> list[ClTask]:
        with self._lock:
            return list(self._tasks)

    def __len__(self) -> int:
        with self._lock:
            return len(self._tasks)


class _Consumer(threading.Thread):
    """Per-chip consumer (reference: DevicePoolThread,
    ClPipeline.cs:4740-5080): private cruncher, greedy pulls from the shared
    pipe plus a pinned queue for device-selected/broadcast tasks.

    With ``fine_grained_queue_control`` on, the consumer throttles on real
    in-flight depth — it claims no new task while
    ``count_markers_remaining() >= queue_limit`` (reference:
    ``markersRemaining() < queueLimit`` gating, ClPipeline.cs:4899-4909).
    Markers retire on actual device completion (utils/markers.py), so this
    bounds device work in flight, not host dispatch."""

    def __init__(self, pool: "ClDevicePool", device: Device, index: int):
        super().__init__(daemon=True, name=f"devpool-{index}")
        self.pool = pool
        self.device = device
        self.index = index
        self.pinned: "queue.Queue[ClTask | None]" = queue.Queue()
        self.cruncher = NumberCruncher(Devices([device]), pool.kernel_source)
        if pool.fine_grained_queue_control:
            self.cruncher.fine_grained_queue_control = True
        self.tasks_done = 0
        self.max_inflight_seen = 0
        self._halt = False

    def _throttle(self) -> None:
        if not self.pool.fine_grained_queue_control:
            return
        while not self._halt:
            depth = self.cruncher.count_markers_remaining()
            self.max_inflight_seen = max(self.max_inflight_seen, depth)
            if depth < self.pool.queue_limit:
                return
            time.sleep(0.0005)

    def run(self) -> None:  # pragma: no cover - exercised via pool tests
        while not self._halt:
            # claim up to the ADAPTIVE queue depth per wake (the reference's
            # pool-progress heuristic shrinks per-device claims as the pool
            # drains so the tail stays balanced, ClPipeline.cs:4188-4230)
            self._throttle()
            batch: list[ClTask] = []
            try:
                batch.append(self.pinned.get_nowait())
            except queue.Empty:
                try:
                    batch.append(self.pool._pipe.get(timeout=0.05))
                except queue.Empty:
                    continue
            while len(batch) < self.pool._adaptive_depth():
                try:
                    batch.append(self.pool._pipe.get_nowait())
                except queue.Empty:
                    break
            for task in batch:
                try:
                    self._throttle()
                    _tt = TRACER.t0("pool-task")
                    task.compute(self.cruncher)
                    TRACER.record(
                        "pool-task", _tt, cid=task.compute_id,
                        lane=self.index,
                        tag=(f"task{task.task_id}" if task.tenant is None
                             else f"task{task.task_id}@{task.tenant}"),
                    )
                    # tenant-tagged tasks attribute to the serving
                    # tier's per-tenant series; untagged tasks keep the
                    # exact pre-serving series (no label-set change)
                    if task.tenant is not None:
                        REGISTRY.counter(
                            "ck_pool_tasks_total",
                            "device-pool tasks completed",
                            lane=self.index, tenant=task.tenant,
                        ).inc()
                    else:
                        REGISTRY.counter(
                            "ck_pool_tasks_total",
                            "device-pool tasks completed",
                            lane=self.index,
                        ).inc()
                    self.tasks_done += 1
                    if task.callback is not None:
                        task.callback(task)
                except Exception as e:  # surface through the pool
                    # under the inflight condition's lock: finish()'s
                    # error swap must never interleave with an append
                    # (ckcheck lockset finding — the list rode bare
                    # GIL-atomicity before)
                    with self.pool._inflight_lock:
                        self.pool._errors.append(e)
                    # one bad task must not poison this chip's private
                    # cruncher for the remaining tasks (the per-compute
                    # error gate is for user-owned crunchers)
                    self.cruncher.reset_errors()
                finally:
                    self.pool._done_one()

    def stop(self) -> None:
        self._halt = True


class ClDevicePool:
    """Greedy batch scheduler over chips (reference: ClDevicePool,
    ClPipeline.cs:3933-4737).

    One consumer thread + private single-chip :class:`NumberCruncher` per
    device; a producer thread walks enqueued task pools, interprets control
    tasks, and pushes compute tasks to the shared pipe.
    """

    def __init__(
        self,
        devices: Devices,
        kernel_source,
        pool_type: PoolType = PoolType.DEVICE_COMPUTE_AT_WILL,
        max_queues_per_device: int = 4,
        fine_grained_queue_control: bool = False,
        queue_limit: int = 8,
        backpressure: int = 0,
    ):
        """``fine_grained_queue_control`` enables marker-based in-flight
        throttling per chip with ``queue_limit`` as the depth bound
        (reference: ClPipeline.cs:4899-4909).  ``backpressure`` bounds the
        shared pipe (producer blocks when full; 0 = auto: 8 slots per
        device) so a task storm cannot enqueue unboundedly."""
        if pool_type is not PoolType.DEVICE_COMPUTE_AT_WILL:
            raise CekirdeklerError(
                "only DEVICE_COMPUTE_AT_WILL is implemented (the reference's "
                "ROUND_ROBIN is unimplemented there too, ClPipeline.cs:3792-3807)"
            )
        self.kernel_source = kernel_source
        self.max_queues_per_device = max_queues_per_device
        self.fine_grained_queue_control = fine_grained_queue_control
        self.queue_limit = max(1, queue_limit)
        cap = backpressure if backpressure > 0 else 8 * max(1, len(devices))
        self._pipe: "queue.Queue[ClTask]" = queue.Queue(maxsize=cap)
        self._pools: "queue.Queue[ClTaskPool]" = queue.Queue()
        self._errors: list[Exception] = []
        self._inflight = 0
        self._inflight_lock = threading.Condition()
        # append-only under _consumers_lock; len()/iteration reads are
        # GIL-atomic snapshots that may miss a hot-added chip for one
        # wake — the adaptive-depth heuristic tolerates that by design
        # ckcheck: ok append-only list; snapshot reads tolerate staleness
        self._consumers: list[_Consumer] = []
        self._consumers_lock = threading.Lock()
        for d in devices:
            self._add_consumer(d)
        self._producer = threading.Thread(target=self._produce, daemon=True, name="devpool-producer")
        self._running = True
        self._producer.start()

    def _adaptive_depth(self) -> int:
        """Per-wake claim depth from pool progress: claim deep while much
        work remains, shrink to 1 near the tail so the last tasks spread
        across chips (reference heuristic, ClPipeline.cs:4188-4230)."""
        with self._inflight_lock:
            remaining = self._inflight
        n = max(1, len(self._consumers))
        return max(1, min(self.max_queues_per_device, remaining // (2 * n)))

    # -- device management ---------------------------------------------------
    def _add_consumer(self, device: Device) -> None:
        c = _Consumer(self, device, len(self._consumers))
        self._consumers.append(c)
        c.start()

    def add_device(self, device: Device) -> None:
        """Hot-add a chip mid-run (reference: ClPipeline.cs:4333-4390)."""
        with self._consumers_lock:
            self._add_consumer(device)

    @property
    def num_devices(self) -> int:
        return len(self._consumers)

    def tasks_done_per_device(self) -> list[int]:
        return [c.tasks_done for c in self._consumers]

    def max_inflight_depth(self) -> int:
        """Largest marker-observed in-flight depth any chip reached — with
        fine-grained control on, bounded by ``queue_limit`` + one task's
        dispatch burst."""
        return max((c.max_inflight_seen for c in self._consumers), default=0)

    # -- accounting ----------------------------------------------------------
    def _dispatch_one(self) -> None:
        with self._inflight_lock:
            self._inflight += 1

    def _done_one(self) -> None:
        with self._inflight_lock:
            self._inflight -= 1
            self._inflight_lock.notify_all()

    def _drain(self) -> None:
        with self._inflight_lock:
            while self._inflight > 0:
                self._inflight_lock.wait(timeout=0.5)

    # -- producer ------------------------------------------------------------
    def _produce(self) -> None:  # pragma: no cover - exercised via tests
        while self._running:
            try:
                pool = self._pools.get(timeout=0.05)
            except queue.Empty:
                continue
            selected: int | None = None
            serial = False
            for task in pool.snapshot():
                tt = task.task_type
                if tt is ClTaskType.DEVICE_SELECT_BEGIN:
                    selected = task.select_device
                    continue
                if tt is ClTaskType.DEVICE_SELECT_END:
                    selected = None
                    continue
                if tt is ClTaskType.GLOBAL_SYNCHRONIZATION:
                    self._drain()
                    continue
                if tt is ClTaskType.SERIAL_MODE_BEGIN:
                    serial = True
                    continue
                if tt is ClTaskType.SERIAL_MODE_END:
                    serial = False
                    continue
                if tt is ClTaskType.BROADCAST:
                    with self._consumers_lock:
                        targets = list(self._consumers)
                    for c in targets:
                        self._dispatch_one()
                        c.pinned.put(task)
                    self._drain()
                    continue
                # plain compute
                self._dispatch_one()
                if selected is not None:
                    with self._consumers_lock:
                        if not (0 <= selected < len(self._consumers)):
                            self._done_one()
                            with self._inflight_lock:  # the errors lock
                                self._errors.append(
                                    CekirdeklerError(
                                        f"device_select index {selected} "
                                        "out of range")
                                )
                            continue
                        self._consumers[selected].pinned.put(task)
                else:
                    self._pipe.put(task)
                if serial:
                    self._drain()
            self._pools.task_done()

    # -- public API ----------------------------------------------------------
    def enqueue_task_pool(self, pool: ClTaskPool) -> None:
        """Queue a pool for execution (reference: enqueueTaskPool,
        ClPipeline.cs:4400-4409)."""
        self._pools.put(pool)

    def finish(self) -> None:
        """Block until all enqueued pools are fully executed (reference:
        finish, ClPipeline.cs:4433+)."""
        # ckcheck: ok queue.Queue.join has no timeout form; consumer
        # threads are daemons dispose() stops, and task_done fires in
        # their finally — finish() blocking until then is the contract
        self._pools.join()
        self._drain()
        with self._inflight_lock:
            errs, self._errors = self._errors, []
        if errs:
            raise errs[0]

    def dispose(self) -> None:
        self._running = False
        for c in self._consumers:
            c.stop()
        for c in self._consumers:
            c.join(timeout=2.0)
        for c in self._consumers:
            c.cruncher.dispose()

    def __enter__(self) -> "ClDevicePool":
        return self

    def __exit__(self, *exc) -> None:
        self.dispose()
