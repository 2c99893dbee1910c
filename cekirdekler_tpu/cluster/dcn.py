"""Multi-host DCN tier over the JAX distributed runtime.

The primary multi-host path (SURVEY.md §7 step 6, §5.8): the same
``compute()`` surface as :class:`ClusterAccelerator`, but spanning the N
*processes* of a JAX distributed job — each host computes its balanced
share on its process-local chips via a local :class:`NumberCruncher`, and
written ranges are exchanged with **XLA collectives over DCN** (an
all-gather jitted across the global device set) instead of the TCP tier's
hand-framed sockets.  The TCP tier (`accelerator.py`/`server.py`) remains
the reference-parity fallback for hosts outside a JAX distributed job.

Reference analogue: ``ClusterAccelerator.compute()``
(ClusterAccelerator.cs:170-355) driving remote ``Cores`` over
``NetworkBuffer`` marshaling (ClCruncherServerThread.cs:147-250).  Design
divergences, all TPU-pod idioms:

- **SPMD, not master/worker**: every process runs the same program and the
  same balancer arithmetic on identically all-gathered timings, so the
  per-compute-id splits agree everywhere without a control channel — the
  jax.distributed coordinator replaces SETUP/COMPUTE framing entirely.
- **Step-quantized shares**: per-process step = local device count ×
  local_range; the LCM-step :class:`ClusterLoadBalancer` is reused as-is.
  The remainder share goes to process 0 (the reference's "mainframe").
- **write_all single-owner rule**: process 0 owns write_all arrays
  (broadcast_one_to_all), mirroring the TCP tier's rule that remote nodes
  never return write_all payloads (server.py).
- **Restart-shaped elasticity**: jax.distributed jobs cannot lose or add
  processes MID-RUN, so elasticity here is preemption-shaped
  (``cluster/elastic.py``, ISSUE 13): the job checkpoints each window's
  partition state (atomic tmp+rename), a preempted job restarts —
  possibly with a different process count — resumes from the last
  complete window (:meth:`DistributedAccelerator.resume_elastic`), and
  the membership change is recorded as replayable
  ``member-leave``/``member-join`` decisions whose outputs are the new
  LCM-step re-split.  A kill-and-rejoin run converges to the
  bit-identical image of an undisturbed one
  (tests/_dcn_elastic_worker.py).

Testable without a pod: 2 processes × 4 virtual CPU devices each, with
``gloo`` cross-process collectives (tests/test_dcn.py).
"""

from __future__ import annotations

import functools as _functools
import time
from typing import Sequence

import numpy as np

from ..arrays.clarray import ClArray, ParameterGroup
from ..core.cruncher import NumberCruncher
from ..errors import CekirdeklerError, ComputeValidationError
from ..hardware import Device, Devices
from ..metrics.registry import REGISTRY
from ..trace.spans import TRACER
from .accelerator import IComputeNode
from .balancer import ClusterLoadBalancer

__all__ = ["initialize", "DistributedAccelerator"]


@_functools.lru_cache(maxsize=4)
def _process_mesh():
    """1-D mesh with ONE device per process (each process's first local
    device, in process order) — the cross-host exchange lattice.  Cached:
    membership of a jax.distributed job is static."""
    import jax
    from jax.sharding import Mesh

    first: dict[int, object] = {}
    for d in jax.devices():  # coordinator-assigned order, same everywhere
        first.setdefault(d.process_index, d)
    devs = [first[p] for p in sorted(first)]
    return Mesh(np.array(devs), ("x",))


@_functools.lru_cache(maxsize=4)
def _replicator(mesh):
    """One compiled all-gather (replicating identity) per mesh — a fresh
    ``jax.jit`` per call would re-trace and re-compile on every exchange,
    a cross-host synchronization point on the hot path."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    return jax.jit(lambda a: a, out_shardings=NamedSharding(mesh, P()))


@_functools.lru_cache(maxsize=4)
def _reducer(mesh):
    """One compiled replicating row-sum per mesh (the broadcast path)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    return jax.jit(
        lambda a: a.sum(axis=0).astype(jnp.uint8),
        out_shardings=NamedSharding(mesh, P()),
    )


def initialize(
    coordinator_address: str,
    num_processes: int,
    process_id: int,
    *,
    cpu_collectives: str = "gloo",
) -> None:
    """Join the JAX distributed job (idempotent).

    Wraps ``jax.distributed.initialize`` with the CPU-collectives
    implementation configured first — without it a multi-process CPU
    backend (the virtual test rig) comes up with single-process visibility
    and every cross-process collective silently degenerates."""
    import jax

    if jax.distributed.is_initialized():
        return  # already joined
    if cpu_collectives:
        jax.config.update(
            "jax_cpu_collectives_implementation", cpu_collectives
        )
    jax.distributed.initialize(
        coordinator_address, num_processes=num_processes,
        process_id=process_id,
    )


class DistributedAccelerator(IComputeNode):
    """N host processes behaving as ONE device over DCN.

    Construct AFTER :func:`initialize` (or ``jax.distributed.initialize``)
    in every process of the job, then use exactly like a
    :class:`NumberCruncher`-backed node: ``setup_nodes(src)`` once,
    ``compute(...)`` per step.  Every process must make the same calls in
    the same order (SPMD) — the collectives inside are global.

    ``timing_hook(compute_id, share, wall_ms) -> float`` optionally
    replaces the measured local wall time fed to the balancer — the same
    deterministic-bench-injection seam ``benchrig.compute_path_proof``
    uses, because on shared-core virtual rigs wall time measures scheduler
    contention, not work.
    """

    def __init__(self, local_devices: Devices | None = None,
                 timing_hook=None):
        import jax

        self.pid = jax.process_index()
        self.nproc = jax.process_count()
        if local_devices is None:
            local_devices = Devices(Device(d) for d in jax.local_devices())
        if not len(local_devices):
            raise CekirdeklerError("no process-local devices")
        self.local_devices = local_devices
        self.timing_hook = timing_hook
        self.cruncher: NumberCruncher | None = None
        self.kernel_source: str | None = None
        self.proc_device_counts: list[int] = []
        self.balancers: dict[int, ClusterLoadBalancer] = {}
        self.ranges: dict[int, list[int]] = {}
        self.timings: dict[int, list[float]] = {}

    # -- collective helpers --------------------------------------------------
    @staticmethod
    def _allgather(value: np.ndarray) -> np.ndarray:
        """Per-process all-gather → ``[nproc, *value.shape]`` via a jitted
        XLA all-gather over one device per process (the DCN path).

        Built directly on a process-representative device mesh rather than
        ``multihost_utils.process_allgather``: the latter reshapes the
        device list to (nproc, local_count) and so requires every process
        to hold the SAME number of devices — true on TPU pods, not on
        ad-hoc CPU fleets or asymmetric test rigs.  Each process's payload
        rides its first local device, so exactly ``nproc`` rows move over
        DCN (no zero rows for the other local chips).

        Payloads cross as raw bytes: ``device_put`` canonicalizes
        int64/float64 to 32-bit when ``jax_enable_x64`` is off (the
        production default), which would silently wrap/round 64-bit host
        arrays — the TCP tier ships raw bytes, and the two tiers must
        agree."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        _tt = TRACER.t0("dcn-exchange")
        _t0 = time.perf_counter()
        value = np.ascontiguousarray(value)
        raw = value.view(np.uint8)
        mesh = _process_mesh()
        nproc = mesh.devices.size
        my_dev = jax.local_devices()[0]
        shard = jax.device_put(raw[None], my_dev)
        garr = jax.make_array_from_single_device_arrays(
            (nproc,) + raw.shape, NamedSharding(mesh, P("x")), [shard]
        )
        gathered = np.asarray(_replicator(mesh)(garr))
        REGISTRY.counter(
            "ck_dcn_exchange_bytes_total", "bytes moved over DCN collectives",
            op="allgather",
        ).inc(raw.nbytes * nproc)
        REGISTRY.histogram(
            "ck_dcn_exchange_seconds", "per-collective wall latency",
            op="allgather",
        ).observe(time.perf_counter() - _t0)
        TRACER.record(
            "dcn-exchange", _tt, tag=f"allgather {raw.nbytes}B x{nproc}"
        )
        return gathered.view(value.dtype).reshape((nproc,) + value.shape)

    @staticmethod
    def _broadcast0(value: np.ndarray) -> np.ndarray:
        """Process 0's copy, everywhere (write_all single-owner rule).

        An owner-masked byte psum over the process mesh, NOT an N-row
        all-gather API call: non-owners contribute exact zeros, so the
        replicated row-sum IS the owner's payload.  INTENT is the
        reduce+broadcast traffic shape (O(M) per link vs O(N·M) for
        gathering N full copies), but on a 1-D process mesh XLA may
        still lower the replicated row-sum as all-gather + local reduce
        — the per-link byte claim is unverified on this backend (ADVICE
        r5 #3); what the masked-psum form guarantees is the single-owner
        SEMANTICS: every process ends with exactly process 0's bytes."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        _tt = TRACER.t0("dcn-exchange")
        _t0 = time.perf_counter()
        value = np.ascontiguousarray(value)
        raw = value.view(np.uint8)
        mesh = _process_mesh()
        nproc = mesh.devices.size
        mine = raw if jax.process_index() == 0 else np.zeros_like(raw)
        shard = jax.device_put(mine[None], jax.local_devices()[0])
        garr = jax.make_array_from_single_device_arrays(
            (nproc,) + raw.shape, NamedSharding(mesh, P("x")), [shard]
        )
        out = np.asarray(_reducer(mesh)(garr))
        REGISTRY.counter(
            "ck_dcn_exchange_bytes_total", "bytes moved over DCN collectives",
            op="broadcast0",
        ).inc(raw.nbytes)
        REGISTRY.histogram(
            "ck_dcn_exchange_seconds", "per-collective wall latency",
            op="broadcast0",
        ).observe(time.perf_counter() - _t0)
        TRACER.record(
            "dcn-exchange", _tt, tag=f"broadcast0 {raw.nbytes}B"
        )
        return out.view(value.dtype).reshape(value.shape)

    def barrier(self, tag: str = "ck_dcn_barrier") -> None:
        """Cross-process sync point (reference: the TCP tier's synchronous
        request/reply implies one; here it is explicit).

        Rides the tier's own :meth:`_allgather` rather than
        ``multihost_utils.sync_global_devices``: the latter reshapes the
        device list to ``(nproc, local_count)`` and so requires every
        process to hold the SAME device count — the exact constraint
        ``_allgather`` exists to avoid, and elastic rejoins
        (``resume_elastic``) are routinely asymmetric.  The gathered tag
        hash doubles as the name-mismatch assertion."""
        import zlib

        h = np.asarray([zlib.crc32(tag.encode())], np.uint32)
        gathered = self._allgather(h)
        if not (gathered == h[0]).all():
            raise CekirdeklerError(
                f"barrier tag mismatch across processes ({tag!r}): "
                f"{gathered.reshape(-1).tolist()}")

    # -- IComputeNode --------------------------------------------------------
    def setup_nodes(self, kernel_source: str) -> None:
        """Compile the kernel locally and agree on the per-process step
        table (reference: setupNodes, ClusterAccelerator.cs:364-443 —
        minus the socket handshake the coordinator already did)."""
        self.kernel_source = kernel_source
        self.cruncher = NumberCruncher(self.local_devices, kernel_source)
        counts = self._allgather(
            np.asarray([len(self.local_devices)], np.int64)
        )
        self.proc_device_counts = [int(c) for c in counts.reshape(-1)]

    @property
    def num_nodes(self) -> int:
        return self.nproc

    def compute(
        self,
        kernel_names: str | Sequence[str],
        params: Sequence[ClArray],
        compute_id: int,
        global_range: int,
        local_range: int = 256,
        values=(),
    ) -> None:
        if self.cruncher is None:
            raise CekirdeklerError("setup_nodes() must run before compute()")
        names = (
            kernel_names.split()
            if isinstance(kernel_names, str)
            else list(kernel_names)
        )
        if global_range % local_range != 0:
            raise ComputeValidationError(
                f"global_range ({global_range}) must be divisible by "
                f"local_range ({local_range})"
            )
        params = list(params)

        # identical balancer state on every process: inputs are the
        # all-gathered timings of the previous call and the shared range
        # table, so the arithmetic below agrees without coordination
        bal = self.balancers.get(compute_id)
        if bal is None:
            steps = [c * local_range for c in self.proc_device_counts]
            bal = ClusterLoadBalancer(steps)
            self.balancers[compute_id] = bal
            shares, remainder = bal.equal_split(global_range)
        else:
            prev = self.ranges[compute_id]
            times = self.timings.get(compute_id, [1.0] * self.nproc)
            shares, remainder = bal.rebalance(prev, times, global_range)
        shares = list(shares)
        shares[0] += remainder  # process 0 is the mainframe
        refs = np.concatenate([[0], np.cumsum(shares)]).astype(int)
        self.ranges[compute_id] = shares

        my_share = shares[self.pid]
        my_off = int(refs[self.pid])
        _tt = TRACER.t0("enqueue")
        t0 = time.perf_counter()
        if my_share > 0:
            group = ParameterGroup(params)
            group.compute(
                self.cruncher, compute_id, names, my_share, local_range,
                global_offset=my_off, values=values,
            )
        wall_ms = (time.perf_counter() - t0) * 1000.0
        if self.timing_hook is not None:
            wall_ms = float(self.timing_hook(compute_id, my_share, wall_ms))

        # result exchange: every process contributes its written range,
        # padded to the max share so the all-gather is rectangular; the
        # collective sequence below is identical on every process (it
        # depends only on the shared share table and array flags)
        max_elems = int(max(shares))
        for p in params:
            if not (p.flags.write and not p.flags.read_only):
                continue
            host = p.host()
            if p.flags.write_all:
                # single-owner rule (server.py): process 0's copy wins
                np.copyto(host, self._broadcast0(host))
                continue
            epw = p.flags.elements_per_work_item
            pad = np.zeros(max_elems * epw, host.dtype)
            if my_share > 0:
                lo = my_off * epw
                n = my_share * epw
                pad[:n] = host[lo:lo + n]
            gathered = self._allgather(pad)
            for j in range(self.nproc):
                if j == self.pid or shares[j] <= 0:
                    continue
                lo = int(refs[j]) * epw
                n = shares[j] * epw
                host[lo:lo + n] = gathered[j, :n]

        times = self._allgather(np.asarray([wall_ms], np.float64))
        self.timings[compute_id] = [float(t) for t in times.reshape(-1)]
        TRACER.record(
            "enqueue", _tt, cid=compute_id,
            tag=f"dcn p{self.pid}/{self.nproc} share{my_share}",
        )

    # -- elastic membership & window checkpoints (cluster/elastic.py) --------
    def member_table(self, local_range: int) -> dict:
        """This job's elastic-membership roster: ``{"p<i>": step}`` with
        step = process i's device count × ``local_range`` (the LCM-step
        table's row).  Requires :meth:`setup_nodes` (the agreed
        device-count table is the input)."""
        if not self.proc_device_counts:
            raise CekirdeklerError(
                "setup_nodes() must run before member_table()")
        return {
            f"p{i}": c * local_range
            for i, c in enumerate(self.proc_device_counts)
        }

    def establish_membership(self, local_range: int,
                             prev_steps: Sequence[int] | None = None,
                             total: int | None = None):
        """Epoch-numbered membership for this job (elastic.Membership).

        ``prev_steps`` is a previous incarnation's member-step table
        (from a window checkpoint): when it differs from the current
        roster, the leave/join transitions — a preempted member gone,
        a rejoined one back, a resized one re-split — are recorded as
        replayable decisions carrying the new LCM-step re-split over
        ``total``.  Every process runs the same reconciliation on the
        same inputs (SPMD), so the recorded sequences agree."""
        from .elastic import Membership

        m = Membership()
        if prev_steps:
            m.establish({
                f"p{i}": int(s) for i, s in enumerate(prev_steps)})
            m.sync(self.member_table(local_range), total)
        else:
            m.establish(self.member_table(local_range))
        return m

    def checkpoint_window(self, root: str, window: int, arrays: dict,
                          local_range: int) -> str | None:
        """Persist one completed window's partition state (process 0
        only — post-exchange every process holds identical host
        arrays, and N writers racing one step dir would be N-1 wasted
        renames).  Callers barrier AFTER this so no process runs ahead
        of a checkpoint that may need to be resumed."""
        if self.pid != 0:
            return None
        from .elastic import save_window

        steps = [c * local_range for c in self.proc_device_counts]
        return save_window(root, window, arrays, member_steps=steps)

    def resume_elastic(self, root: str, local_range: int,
                       total: int | None = None) -> dict | None:
        """Resume a preempted job: load the newest COMPLETE window
        checkpoint (torn newest falls back — utils/checkpoint.py),
        reconcile membership against the checkpointed roster (recorded
        leave/join re-splits), warm the local cruncher's ladder set
        from the persistent executable cache when ``CK_COMPILE_CACHE``
        is armed (core/compilecache.py — a rejoining member re-traces
        the fleet's persisted signature mix and every XLA compile loads
        from disk, so the rejoin pays no fresh compile wall), and
        return ``{"window", "arrays", "member_steps", "membership"}``
        — or None on a fresh start."""
        from .elastic import resume_window

        state = resume_window(root)
        membership = self.establish_membership(
            local_range,
            prev_steps=(state or {}).get("member_steps"),
            total=total)
        if self.cruncher is not None:
            from ..core.compilecache import CACHE, warm_from_disk

            if CACHE.enabled:
                warm = warm_from_disk(self.cruncher.cores)
                if state is not None:
                    state["cache_warm"] = warm
        if state is None:
            return None
        state["membership"] = membership
        return state

    # -- introspection (obs/) ------------------------------------------------
    def health_report(self) -> dict:
        """This process's lane-health verdicts (``Cores.health_report``
        of the local cruncher; ``{}`` before ``setup_nodes``).
        ``trace.gather_cluster(acc)`` ships this automatically, so the
        DCN tier sees every process's lane verdicts merged on one table
        (``obs.health.cluster_health_table``)."""
        if self.cruncher is None:
            return {}
        return self.cruncher.cores.health_report()

    def serve_debug(self, port: int = 0, host: str = "127.0.0.1"):
        """Start this process's live debug endpoints over the local
        cruncher's scheduler (obs/debugserver.py) — one plane per DCN
        process; the cluster-wide view is the aggregated snapshot."""
        if self.cruncher is None:
            raise CekirdeklerError("setup_nodes() must run before serve_debug()")
        return self.cruncher.cores.serve_debug(port=port, host=host)

    def compute_timing(self, compute_id: int) -> list[float]:
        return list(self.timings.get(compute_id, []))

    def ranges_of(self, compute_id: int) -> list[int]:
        return list(self.ranges.get(compute_id, []))

    def dispose(self) -> None:
        if self.cruncher is not None:
            self.cruncher.dispose()
            self.cruncher = None
