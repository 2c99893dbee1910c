"""Reads across lanes: who holds the current elements, and the strips that
cross before a launch.

Per array, the lane whose buffer holds the newest value of each element
interval, from the ranges of every compute and the arrays its kernels store
to.  Kept from the first compute whose kernels read beyond their own range
(the analysis' proved reach); before a lane's launch of such a compute the
parts of its reach that another lane wrote last are fetched from THAT lane's
buffer, device to device, and only what no lane wrote comes from the host.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Sequence

from ..arrays.clarray import ClArray
from ..errors import ComputeValidationError
from ..kernel.registry import KernelProgram
from ..trace.spans import TRACER
from .worker import Worker, launch_ladder

__all__ = ["Exchange"]


@dataclass
class _LanePlan:
    """What one lane does before its launch of a compute whose kernels read
    across lanes (:meth:`Exchange.stage`)."""

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

    def make_current(self, w: Worker, params: Sequence[ClArray],
                     compute_id: int, windowed: bool) -> float:
        """A lane's half of :meth:`Exchange.stage`, under its phase lock:
        lay in what was staged for it (a synchronous compute's uploads;
        the strips cut from the lanes that wrote them last: one ``halo``
        span a compute that fetched any) and, inside an enqueue window,
        upload what its coverage lacks of the intervals no lane holds.
        Returns the seconds the uploads took."""
        t0 = time.perf_counter()
        for staged in self.uploads:
            w.commit_upload(staged)
        for idx, p in enumerate(params):
            if idx not in self.host:
                w.ensure_resident(p)
            elif windowed:  # (a synchronous compute staged them)
                for lo, hi in self.host[idx]:
                    if not w.upload_covers(p, lo, hi - lo):
                        w.upload(p, lo, hi - lo, (lo, hi) == (0, p.size))
        t_up = time.perf_counter() - t0
        if self.strips:
            _th = TRACER.t0("halo")
            how = {w.lay_strip(src, p, strip, lo)
                   for src, p, strip, lo in self.strips}
            if _th:
                TRACER.record(
                    "halo", _th, cid=compute_id, lane=w.index,
                    tag="+".join(sorted(how)),
                    bytes=sum(s[2].nbytes for s in self.strips),
                    src="+".join(str(k) for k in sorted(
                        {s[0].index for s in self.strips})))
        return t_up


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


class Exchange:
    """``owners``: ``id(array) -> (array, sorted disjoint (lo, hi,
    lane))``, the map the enqueue window peeks into (a scheduler that
    tracks any array starts no window on the ladder).  Reads and writes
    hold ``lock``, the scheduler's (``core/window.py`` has the table); a
    lane's upload coverage stays under its worker lock."""

    def __init__(self, program: KernelProgram, workers: list[Worker],
                 pool: ThreadPoolExecutor, lock: threading.Lock,
                 owners: dict[int, tuple]):
        self.program = program
        self.workers = workers
        self.pool = pool
        self.lock = lock
        self.owners = owners

    def reach_elements(self, verdict, kernel_names, value_args) -> dict:
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

    def stage(
        self, exchange, params, global_offset: int, ranges, refs,
        step: int, windowed: bool,
    ) -> dict:
        """Before the lanes of a compute that reads across lanes launch:
        ``[offset - reach, offset + size + reach)`` of every array its
        kernels read must be current on each lane.  Returns one
        :class:`_LanePlan` a lane.

        Inside an enqueue window (``windowed``) the intervals that ANOTHER
        lane wrote last come from that lane's buffer, which its launch of
        the compute before left, and only what no lane holds comes from the
        host.  All strips are CUT here (``Worker.cut_strip``: a slice on
        the writer's device, nothing waits), on the caller's thread, before
        any lane's phase of this compute is submitted: a phase replaces its
        lane's buffers, and a neighbour must read what the compute before
        left.  Each lane's phase then brings its strips over and lays them
        in (``Worker.lay_strip``), the lanes side by side.  A range that
        moved is the same fetch: the gained strip's last writer is the lane
        that held it.

        A synchronous compute takes everything from the host (which the
        compute before made current), widened by the reach where
        ``partial_read`` sends the slice alone; every lane's host reads
        are staged here and joined before any phase starts, because a
        phase ends by writing its lane's results into the same host
        arrays: a lane that uploaded late read its neighbour's rows of
        the NEXT step."""
        verdict, reach = exchange
        with self.lock:
            owned = {pos: self.owners.get(id(params[pos]), (None, ()))[1]
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

    def note_writers(self, exchange, params, global_offset: int,
                     ranges, refs) -> None:
        """After a compute's launches are out: each lane holds the newest
        elements of its own range of every array the kernels store to
        (the verdict's word for a compute that reads across lanes; for any
        other compute, every array in the map that is not ``read_only``)."""
        with self.lock:
            if exchange is not None:
                stored = [params[pos] for pos in exchange[0].writes]
            else:
                stored = [p for p in params if id(p) in self.owners
                          and not p.flags.read_only]
            for p in stored:
                epw = p.flags.elements_per_work_item
                owned = self.owners.get(id(p), (p, ()))[1]
                for i, size in enumerate(ranges):
                    if size > 0:
                        lo = (global_offset + refs[i]) * epw
                        owned = _own_assign(
                            owned, lo, min(p.size, lo + size * epw), i)
                self.owners[id(p)] = (p, owned)

    def clear(self) -> None:
        with self.lock:
            self.owners.clear()
