"""The worst chip's idle time put down to what the PROGRAM was doing: the
shared reduction behind the per-layer metrics that read the program's own
spans (``layer_metrics/*_idle_ms_per_call.py``, ``unnamed_idle_share.py``,
``unfused_computes_per_call.py``, ``driver_queue_wait_ms_per_call.py``).

While a ``jax.profiler`` session runs, the program's tracer
(``cekirdekler_tpu/trace/spans.py``) writes every span as an annotation named
``ck/<kind>`` into ``/host:CPU`` of the same ``.xplane.pb`` as the chips' ``XLA
Ops`` lines, on one clock, with metadata (xplane stats): ``lane``, ``cid``,
``tag``, ``win``, and on a pool or driver thread ``queued_us``.  A program that
has no such spans (a parent commit) leaves nothing to read: ``of`` returns
None and every reader leaves its metric out.

The window and the worst chip are ``xplane.Reduced``'s.  That chip's idle gaps
go to the INNERMOST (shortest) ``ck/`` span covering them among the spans of
the caller's thread (the host line that holds the ``bench/`` spans) and the
spans that carry THAT chip's lane on any other thread (pool and driver
threads work for one lane at a time); another lane's ``launch`` explains
nothing about this chip.  Kinds are grouped by layer (``GROUPS``; a kind that
is in no group counts as dispatch), so the groups and the unnamed rest add up
to the chip's idle.  ``checks/test_host_phases.py`` holds all of it to a
hand-made trace.
"""

from __future__ import annotations

import os
from typing import NamedTuple

import xplane

PREFIX = "ck/"
UNNAMED = "unnamed"
DISPATCH = "dispatch"
#: layer group -> span kinds; everything else of the program's is dispatch
GROUPS = {
    "sched": ("schedule", "split", "rebalance"),
    "resync": ("resync", "upload", "download", "upload-chunk",
               "download-chunk", "tune"),
    "fence": ("fence",),
    "compile": ("compile",),
}
_GROUP_OF = {PREFIX + k: g for g, kinds in GROUPS.items() for k in kinds}
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".bench_trace")  # where run.py records


class HostSpan(NamedTuple):
    name: str      # "ck/launch"
    start: float
    end: float
    line: int      # index of the host thread's line
    stats: dict    # lane, cid, tag, win, queued_us, bytes, ...


class Phases(NamedTuple):
    chip: int
    lane: int
    calls: int
    idle_s: float          # the chip's idle inside the window
    by_kind: dict          # "ck/<kind>" | UNNAMED -> idle seconds
    unnamed_by_bench: dict  # bench span (or xplane.OUTSIDE) -> unnamed seconds
    unfused_computes: int  # ck/enqueue spans that took the per-call path
    queue_wait_s: float    # queued_us summed over the lane's closures

    def group_s(self, group: str) -> float:
        """Idle seconds under the group's kinds (``DISPATCH``: every
        ``ck/`` kind that no other group names)."""
        return sum(v for k, v in self.by_kind.items() if k != UNNAMED
                   and _GROUP_OF.get(k, DISPATCH) == group)

    def idle_ms_per_call(self, group: str) -> float:
        return 1e3 * self.group_s(group) / self.calls


def host_lines(profile) -> list[list[HostSpan]]:
    """Per host thread, its ``ck/`` and ``bench/`` spans."""
    lines = []
    for plane in profile.planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            spans = []
            for ev in line.events:
                if ev.name.startswith((PREFIX, "bench/")):
                    t0 = ev.start_ns * 1e-9
                    spans.append(HostSpan(
                        ev.name, t0, t0 + ev.duration_ns * 1e-9, len(lines),
                        dict(ev.stats) if ev.name.startswith(PREFIX) else {}))
            lines.append(spans)
    return lines


def innermost_cover(gaps, spans):
    """Seconds of the (sorted, disjoint) gaps under each span's name, a
    stretch going to the SHORTEST span that covers it; what no span covers
    comes back as intervals.  One sweep over the span boundaries."""
    events = sorted(
        [(s.start, 1, i) for i, s in enumerate(spans) if s.end > s.start]
        + [(s.end, 0, i) for i, s in enumerate(spans) if s.end > s.start])
    out: dict[str, float] = {}
    uncovered: list[tuple[float, float]] = []
    active: set[int] = set()
    at_event = 0
    for a, b in gaps:
        at = a
        while at < b:
            while at_event < len(events) and events[at_event][0] <= at:
                _t, opens, i = events[at_event]
                if opens:
                    active.add(i)
                else:
                    active.discard(i)
                at_event += 1
            nxt = events[at_event][0] if at_event < len(events) else b
            hi = min(b, nxt)
            if active:
                s = spans[min(active,
                              key=lambda i: spans[i].end - spans[i].start)]
                out[s.name] = out.get(s.name, 0.0) + hi - at
            else:
                uncovered.append((at, hi))
            at = hi
    return out, uncovered


def reduce(profile, reduced, lane_of_chip=None) -> Phases | None:
    chip = reduced.worst_chip
    lane = (lane_of_chip or {}).get(chip, chip)
    t0, t1 = reduced.t0, reduced.t1
    lines = host_lines(profile)
    caller = next((i for i, spans in enumerate(lines)
                   if any(s.name == "bench/call" for s in spans)), None)
    ours = [s for spans in lines for s in spans
            if s.name.startswith(PREFIX) and s.end > t0 and s.start < t1
            and (s.line == caller or s.stats.get("lane") == lane)]
    if not ours:
        return None
    ops = []
    for plane in profile.planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        if m is None or int(m.group(1)) != chip:
            continue
        for line in plane.lines:
            if line.name == xplane.OPS_LINE:
                ops += [(ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
    gaps = xplane.gaps_of(xplane.merged(ops, t0, t1), t0, t1)
    by_kind, uncovered = innermost_cover(gaps, ours)
    by_kind[UNNAMED] = sum(b - a for a, b in uncovered)
    bench = [xplane.Span(s.name, s.start, s.end)
             for s in (lines[caller] if caller is not None else [])
             if s.name.startswith("bench/")]
    per_call = [s for s in ours if s.name == PREFIX + "enqueue"
                and s.line == caller and t0 <= s.start < t1
                and not str(s.stats.get("tag", "")).endswith("fused-defer")]
    # a closure's wait rides every span closed inside it: count it once
    waits = {(s.line, s.stats.get("win"), s.stats["queued_us"])
             for s in ours if s.line != caller and "queued_us" in s.stats
             and t0 <= s.start < t1}
    return Phases(chip, lane, reduced.calls, sum(b - a for a, b in gaps),
                  by_kind, xplane.attribute(uncovered, bench),
                  len(per_call), 1e-6 * sum(w[2] for w in waits))


def of(ctx) -> Phases | None:
    """The run's reduction, made once and kept on ``ctx`` for the eight
    readers (they run before ``run.py`` removes the trace)."""
    if not hasattr(ctx, "host_phases"):
        lanes = {w.device.id: w.index for w in ctx.cr.cores.workers}
        ctx.host_phases = reduce(
            xplane._profile(xplane.find_xplane(TRACE_DIR)),  # its one loader
            ctx.reduced, lanes)
        p = ctx.host_phases
        if p is not None:
            def per_call(seconds: dict) -> dict:
                return {k: round(1e3 * v / p.calls, 3) for k, v in sorted(
                    seconds.items(), key=lambda kv: -kv[1])}

            print(f"[bench] host phases: chip {p.chip} (lane {p.lane}) idle "
                  f"{1e3 * p.idle_s / p.calls:.3f} ms a call over {p.calls} "
                  f"calls; ms a call by innermost span {per_call(p.by_kind)}; "
                  f"the unnamed part lies under "
                  f"{per_call(p.unnamed_by_bench)}", flush=True)
    return ctx.host_phases
