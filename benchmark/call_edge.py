"""A synchronous ``compute()``'s edge told from inside the program: the
shared reduction behind ``layer_metrics/call_edge_ms_per_call.py`` and the
log line ``[bench] call edge:`` of a traced run of a per-call cell.

``window_edge`` holds a traced session to causality at every barrier's
``retired`` and reads a window's head and tail on the host's clock alone; a
cell of synchronous, non-windowed calls has no barrier, so it reads nothing
there, and ``host_phases`` lays such a cell's 2-3 ms of host time a call over
a device timeline that sessions hold 0.07-1.4 ms apart (PERF.md s.7).  Since
ISSUE 52 the program marks the lane's half of a synchronous compute as it
marks the caller's (``cekirdekler_tpu/trace/spans.py``, "The lane's half of a
synchronous compute"): ``ck/enqueue`` ``phase-start`` (with ``hop_us``) and
``phase-locked`` on the pool's thread, ``part:call`` / ``part:handed`` around
a ``ck/launch`` span's dispatches (they ride ``ck/engage`` with ``lane``), and
``part:landed`` is the per-call path's ``retired``.  A program without them (a
parent commit) leaves nothing to read: ``reduce`` returns None.

**A compute** is a non-deferred ``ck/enqueue`` span of the caller's thread
inside a ``bench/call``; what the worst chip's lane did for it carries its
``win``.  Cut on the HOST's clock alone, one stretch behind the other:

    open      the ``bench/call``'s start to its first compute's open: the
              harness's loop and ``ClArray.compute``'s own way in
    caller    ``ck/enqueue`` open to ``part:submit``: the verdict,
              ``_ranges_for``, an exchange's strips
    hop       to the lane's ``phase-start``: the pool hop
    lock      to ``phase-locked``: the wait for the lane
    lane      to the first ``ck/launch`` open: ``classify``, the tuner's
              ``choose``, ``ensure_resident``, and any ``ck/upload`` /
              ``ck/upload-chunk`` (their time inside it is named apart)
    prepare   to that launch's ``part:call``: the buffers, the frozen set
    admit     to its ``part:handed``: the dispatch as the runtime admits it
    run       to the compute's LAST ``part:landed``: the kernel, the bytes and
              the wake-up; a streamed compute's later launches and the first
              launch's own tail (``after``, named apart) hide inside it
    copy      to the end of the download span that holds that mark
    wrap      to the lane's ``phase-done``: ``_note_transfer``, the tuner's
              ``observe``, ``end_bench``
    join      to the caller's ``part:note``
    note      to ``ck/enqueue``'s end
    return    to the next compute's open or the ``bench/call``'s end:
              ``_record_perf``, and between a call's computes the harness

A compute that launches nothing (``no_compute_mode``: a traversal's last,
which only brings ``cost`` back) has its ``run`` start at its first
``part:issued``.  A time that lies before the one ahead of it in this order is
taken up to it, so the parts of a call add up to its ``bench/call`` exactly.
Every figure a call is the MEDIAN over the window's calls of the call's own
sum.  **The metric** is a call's ``caller`` to ``admit`` and ``copy`` to
``return``: everything of its computes outside ``run``, the stretches in which
the chip has nothing of this caller's and only the host can hand it something.
No device line is read for it, so no shift or scale of the trace moves it.

**The causal check and the scale**, for a session without a barrier.  Between
compute k's last ``part:landed`` (R) and the first site of compute k+1 that
hands the lane's chip anything (N: its first upload's open or ``part:call``,
whichever comes first) the chip is idle whatever the clocks say: the gap (g0,
g1) of the chip that holds the stretch bounds a shift ``d`` of the device's
timeline by ``N - g1 <= d <= R - g0``; ``window_edge.least_shift`` over all
edges gives the interval, and its width is the slack.  A session's device
clock can also run at another RATE (PERF.md s.7: every device duration 0.675 x
what another machine read): ``scale`` is the device timeline's extent from the
session's first operation to its last over the host's extent from the first
compute's ``part:call`` to the last compute's last ``part:landed``, known to
the slack over the window's length.  The scale is found FIRST (a scale of
0.675 moves an edge by a second, far outside ``window_edge.REACH_S``): inside
``SCALE_TOLERANCE`` of 1 the timeline is left as recorded; else it is
stretched about its first operation laid on that ``part:call``.  The extents
differ by a dispatch-to-start and a wake-up, which is the slack itself, so the
scale is then taken from a grid of parts in a million around the extents'
(``steadiest_scale``): the one under which most stretches lie on a gap and the
gaps' bounds on the shift differ least from edge to edge (a wrong scale makes
them run away with the time), and the shift is looked for there.  Where no
shift serves every edge the line says how many it leaves short, by how much
and before which compute of a call.  The chip's idle is then put to the parts on that timeline: the split that
``dispatch_idle_ms_per_call`` and ``resync_idle_ms_per_call`` cannot give in a
per-call cell.

``python3 benchmark/call_edge.py --workload <cell> --seed N --seconds S`` is a
traced run of the cell through ``run.run_cell`` with this reader added to what
the cell lists: how a per-call cell that does not list the metric
(``mandelbrot_percall_1chip``) prints the line.
``checks/test_call_edge.py`` holds all of it to a trace made by hand.
"""

from __future__ import annotations

import bisect
import statistics
from typing import NamedTuple

import numpy as np

import host_phases
import xplane
from window_edge import (
    REACH_S, first_from, least_shift, part_of, pieces, tag_of)

PREFIX = host_phases.PREFIX
ENQUEUE, ENGAGE, LAUNCH = (PREFIX + k for k in ("enqueue", "engage", "launch"))
UPLOADS = (PREFIX + "upload", PREFIX + "upload-chunk")
DOWNLOADS = (PREFIX + "download", PREFIX + "download-chunk")
CALL = "bench/call"
#: the parts of a compute in their order; ``open`` is the call's own head
PARTS = ("caller", "hop", "lock", "lane", "prepare", "admit", "run", "copy",
         "wrap", "join", "note", "return")
OPEN = "open"
#: what the metric adds up: everything of a compute outside ``run``
EDGE = tuple(p for p in PARTS if p != "run")
#: a scale this near 1 is the slack's, not the clock's
SCALE_TOLERANCE = 5e-3
#: how far around a scale the widest causal interval is looked for
SCALE_REACH = 1e-3
#: a call this much over the median wall is set apart in the log line
SLOW = 1.1


class Compute(NamedTuple):
    start: float    # ``ck/enqueue`` open
    parts: dict     # part -> seconds, in ``PARTS``' order
    stretches: dict  # part -> (from, to) on the host's clock
    upload_s: float  # inside ``lane``
    after_s: float   # the first launch's ``part:handed`` to its end
    called: float | None  # the first ``part:call``: the dispatch anchor
    site: float     # the first site that hands the chip anything
    landed: float   # the last ``part:landed``: the retirement anchor
    nth: int = 0    # its place among its call's computes


class Call(NamedTuple):
    start: float
    end: float
    computes: list
    parts: dict     # part -> seconds, summed over the computes; and ``open``

    @property
    def wall_s(self) -> float:
        return self.end - self.start

    @property
    def edge_s(self) -> float:
        return sum(self.parts[p] for p in EDGE)


class CallEdge(NamedTuple):
    chip: int
    lane: int
    calls: int             # calls cut (every compute of them anchored)
    computes: int
    unanchored: int        # calls left out: a compute read nothing back
    wall_ms: float         # median ``bench/call``
    parts_ms: dict         # part -> median over the calls of the call's sum
    sum_ms: float          # the medians, added up
    sum_gap_ms: float      # the largest |a call's parts - its bench/call|
    middle_ms: dict        # the parts of the call whose wall is the median
    edge_ms: float         # the metric: median of the calls' ``EDGE`` sums
    upload_ms: float       # inside ``lane``, median a call
    after_ms: float        # inside ``run``, median a call
    raw_scale: float | None  # device extent over host extent, as measured
    scale: float           # what the timeline was stretched by (1.0: as is)
    edges: int
    lower_s: float | None
    upper_s: float | None
    shift_s: float
    unserved: int          # edges the shift does not serve, the worst three
    worst: tuple           # (seconds short, place in the call, stretch)
    idle_ms: float | None  # the chip's idle inside a call, median
    idle_parts_ms: dict    # part -> the chip's idle in it, median a call
    slow_calls: int
    slow_parts_ms: dict    # the parts of the calls over SLOW x the median
    other_parts_ms: dict   # ... and of the others

    @property
    def slack_s(self) -> float | None:
        return None if self.lower_s is None else self.upper_s - self.lower_s


def _chain(marks: list) -> list:
    """``(part, when)`` in order, each time taken up to the one before it."""
    out, at = [], marks[0][1]
    for part, when in marks:
        at = max(at, when if when is not None else at)
        out.append((part, at))
    return out


def cut_compute(e, events: list, lane: int, caller: int,
                nxt: float) -> Compute | None:
    """One compute: its ``ck/enqueue`` span ``e``, everything of its ``win``
    on the caller's thread or for the lane, and where the next stretch of
    the caller begins.  None where nothing it did was read back."""
    mine = [s for s in events if s.stats.get("lane") == lane]
    at: dict = {}  # (name, tag) -> when the lane's events of it began (an
    for s in mine:  # instant is a microsecond or two long in a real trace)
        at.setdefault((s.name, tag_of(s)), []).append(s.start)
    for starts in at.values():
        starts.sort()
    spans = [s for s in mine if part_of(s) is None and s.end > s.start]
    launches = sorted((s for s in spans if s.name == LAUNCH),
                      key=lambda s: s.start)
    uploads = [s for s in spans if s.name in UPLOADS]
    downloads = [s for s in spans if s.name in DOWNLOADS]
    landed = [t for kind in DOWNLOADS
              for t in at.get((kind, "part:landed"), ())]
    issued = sorted(t for kind in DOWNLOADS
                    for t in at.get((kind, "part:issued"), ()))
    if not landed:
        return None
    # the caller's half: window_edge's own cut of the span at its marks
    # (head [+ stage] = ``caller``; ``join:done`` and ``note`` behind the
    # lane's ``phase-done``)
    marks = sorted((m for m in events if m.line == caller
                    and m.name == ENGAGE and part_of(m) is not None
                    and e.start <= m.start <= e.end), key=lambda m: m.start)
    done = first_from(at.get((ENQUEUE, "phase-done"), []), e.start,
                      float("inf"))
    cut = {part: (a, b) for part, a, b in pieces(e, marks, done)}
    submit = cut.get("submit", (None, None))[0]
    note = cut.get("note", (None, None))[0]
    started = first_from(at.get((ENQUEUE, "phase-start"), []), e.start,
                         e.end)
    locked = first_from(at.get((ENQUEUE, "phase-locked"), []), e.start,
                        e.end)
    calls = at.get((ENGAGE, "part:call"), [])
    handed = at.get((ENGAGE, "part:handed"), [])
    first = launches[0] if launches else None
    if first is not None:
        opened = first.start
        called = first_from(calls, first.start, first.end)
        given = first_from(handed, first.start, first.end)
        after = first.end - given if given is not None else 0.0
    else:  # nothing launched: the copy to the host is what the chip gets
        opened = called = given = issued[0] if issued else locked
        after = 0.0
    last = max(landed)
    held = [s.end for s in downloads if s.start <= last <= s.end]
    chain = _chain([
        ("caller", e.start), ("hop", submit), ("lock", started),
        ("lane", locked), ("prepare", opened), ("admit", called),
        ("run", given), ("copy", last), ("wrap", max(held, default=last)),
        ("join", done), ("note", note), ("return", e.end), ("", nxt)])
    stretches = {part: (a, b) for (part, a), (_next, b)
                 in zip(chain, chain[1:])}
    a, b = stretches["lane"]
    first_up = min((s.start for s in uploads), default=None)
    sites = [t for t in (first_up, calls[0] if calls else None)
             if t is not None]
    return Compute(
        e.start, {p: b - a for p, (a, b) in stretches.items()},
        stretches,
        sum(max(0.0, min(s.end, b) - max(s.start, a)) for s in uploads),
        after, calls[0] if calls else None,
        min(sites) if sites else stretches["lane"][0], last)


class Busy:
    """A chip's busy union as arrays, asked for the busy seconds inside an
    interval of the HOST's clock under ``host = pivot_host + (device -
    pivot_device) / scale + shift``."""

    def __init__(self, ops: np.ndarray):
        ops = ops[np.argsort(ops[:, 0], kind="stable")]
        start, end = ops[:, 0], np.maximum.accumulate(ops[:, 1])
        first = np.ones(len(ops), bool)
        first[1:] = start[1:] > end[:-1]
        self.start = start[first]
        self.end = end[np.append(first[1:], True)]
        self.cum = np.concatenate(([0.0], np.cumsum(self.end - self.start)))
        self.scale, self.pivot_host, self.pivot_device, self.shift = (
            1.0, 0.0, 0.0, 0.0)

    def to_host(self, t):
        return (self.pivot_host + (t - self.pivot_device) / self.scale
                + self.shift)

    def to_device(self, t):
        return (self.pivot_device
                + (t - self.shift - self.pivot_host) * self.scale)

    def _before(self, t: float) -> float:
        """Busy seconds of the device's own timeline before ``t``."""
        i = int(np.searchsorted(self.start, t, side="right"))
        if i == 0:
            return 0.0
        return float(self.cum[i - 1]
                     + min(t, self.end[i - 1]) - self.start[i - 1])

    def idle_in(self, a: float, b: float) -> float:
        if b <= a:
            return 0.0
        busy = (self._before(self.to_device(b))
                - self._before(self.to_device(a))) / self.scale
        return max(0.0, (b - a) - busy)

    def gaps(self, at_least: float) -> list:
        """The idle gaps between two operations no shorter than
        ``at_least`` host seconds, on the host's clock."""
        g0, g1 = self.end[:-1], self.start[1:]
        keep = (g1 - g0) / self.scale >= at_least
        return [(float(self.to_host(a)), float(self.to_host(b)))
                for a, b in zip(g0[keep], g1[keep])]


def chip_ops(profile, chip: int) -> np.ndarray:
    rows = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
            for plane in profile.planes
            if (m := xplane.DEVICE_PLANE.match(plane.name)) is not None
            and int(m.group(1)) == chip
            for line in plane.lines if line.name == xplane.OPS_LINE
            for ev in line.events]
    return np.asarray(rows, float).reshape(-1, 2)


class Causal(NamedTuple):
    edges: int = 0
    lower_s: float | None = None
    upper_s: float | None = None
    shift_s: float = 0.0
    #: edges that no gap of theirs lets the chosen shift through, and the
    #: worst three: ``(seconds short, the later compute's place in its call,
    #: the stretch's seconds)``
    unserved: int = 0
    worst: tuple = ()


def edge_bounds(busy: Busy, computes: list) -> tuple:
    """Per edge, the ``(lower, upper)`` pairs its candidate gaps put on the
    shift of the timeline as ``busy`` now maps it, unshifted, and what the
    edge is (the later compute's place in its call, the stretch): between a
    compute's last ``part:landed`` and the next one's first dispatch site
    the chip is idle, and the gap that holds the stretch bounds the shift
    (module docstring)."""
    stretches = [(k.landed, n.site, n.nth)
                 for k, n in zip(computes, computes[1:]) if n.site > k.landed]
    if not stretches:
        return [], []
    gaps = busy.gaps(min(n - r for r, n, _nth in stretches) - 1e-9)
    starts = [g[0] for g in gaps]
    bounds, edges = [], []
    for r, n, nth in stretches:
        at = max(bisect.bisect_left(starts, r - REACH_S) - 1, 0)
        pairs = []
        for g0, g1 in gaps[at:]:
            if g0 > n + REACH_S:
                break
            if g1 - g0 >= n - r - 1e-9 and g1 > r - REACH_S:
                pairs.append((n - g1, r - g0))
        if pairs:
            bounds.append(pairs)
            edges.append((nth, n - r))
    return bounds, edges


def causal(busy: Busy, computes: list) -> Causal:
    bounds, edges = edge_bounds(busy, computes)
    if not bounds:
        return Causal()
    shift, picked = least_shift(bounds)
    short = sorted(
        ((min(max(lo - shift, shift - hi, 0.0) for lo, hi in pairs), nth, w)
         for pairs, (nth, w) in zip(bounds, edges)), reverse=True)
    return Causal(
        len(bounds), max(lo for lo, _hi in picked),
        min(hi for _lo, hi in picked), shift,
        sum(v > 1e-9 for v, _nth, _w in short),
        tuple(v for v in short[:3] if v[0] > 1e-9))


def steadiest_scale(busy: Busy, computes: list) -> float | None:
    """The scale near ``busy``'s own under which the two clocks do not
    drift: every edge is held to the ONE gap that overlaps its stretch most,
    which bounds the shift from below by the edge's dispatch-to-start and
    from above by its wake-up; at the right scale those bounds differ from
    edge to edge by the latencies alone, at a wrong one they run away with
    the time.  The scale that lays most stretches on a gap and then spreads
    the bounds least (tenth to ninetieth percentile, so that a paused call
    does not decide) wins.  Two passes over a grid of parts in a million;
    None where no scale of the grid lays any stretch on a gap.  The extents'
    scale is off by the slack over the session's length, which is all a
    scale can be known to."""
    around = busy.scale
    stretches = [(k.landed, n.site)
                 for k, n in zip(computes, computes[1:]) if n.site > k.landed]
    at_least = min(n - r for r, n in stretches) - 1e-9

    def spread(values: list) -> float:
        values.sort()
        last = len(values) - 1
        return values[round(0.9 * last)] - values[int(0.1 * last)]

    def score(scale: float) -> tuple:
        busy.scale = scale
        gaps = busy.gaps(at_least)
        starts = [g[0] for g in gaps]
        lower, upper = [], []
        for r, n in stretches:
            i = max(bisect.bisect_right(starts, n) - 1, 0)
            over = [(min(n, g1) - max(r, g0), g0, g1)
                    for g0, g1 in gaps[max(i - 2, 0):i + 1]
                    if g1 - g0 >= n - r - 1e-9]
            if over and max(over)[0] > 0:
                _o, g0, g1 = max(over)
                lower.append(n - g1)
                upper.append(r - g0)
        if not lower:
            return 0, 0.0, 0.0
        return (len(lower), -(spread(lower) + spread(upper)),
                -abs(scale - around))

    best = None
    for span, step in ((SCALE_REACH, SCALE_REACH / 10),
                       (SCALE_REACH / 10, SCALE_REACH / 40)):
        centre = around if best is None else best[1]
        n = round(span / step)
        top = max((score(s), s) for s in (centre * (1 + i * step)
                                          for i in range(-n, n + 1)))
        if not top[0][0]:
            break
        best = top
    busy.scale = around
    return None if best is None else best[1]


def _medians(calls: list) -> dict:
    """part -> the median, in ms, of the calls' own sums."""
    return {p: 1e3 * statistics.median(c.parts[p] for c in calls)
            for p in (OPEN,) + PARTS} if calls else {}


def reduce(profile, reduced, lane_of_chip=None) -> CallEdge | None:
    chip = reduced.worst_chip
    lane = (lane_of_chip or {}).get(chip, chip)
    t0, t1 = reduced.t0, reduced.t1
    lines = host_phases.host_lines(profile)
    caller = next((i for i, spans in enumerate(lines)
                   if any(s.name == CALL for s in spans)), None)
    ours = [s for spans in lines for s in spans if s.name.startswith(PREFIX)
            and (s.line == caller or s.stats.get("lane") == lane)]
    if caller is None or not any(
            s.name == ENQUEUE and tag_of(s) == "phase-start" for s in ours):
        return None
    by_win: dict = {}
    for s in ours:
        by_win.setdefault(s.stats.get("win"), []).append(s)
    on_caller = sorted(lines[caller], key=lambda s: s.start)
    spans = [s for s in on_caller if s.name == ENQUEUE and s.end > s.start
             and part_of(s) is None
             and not tag_of(s).endswith("fused-defer")]
    opens = [s.start for s in spans]
    calls, every, unanchored = [], [], 0
    for c in (s for s in on_caller if s.name == CALL
              and t0 <= s.start and s.end <= t1):
        lo = bisect.bisect_left(opens, c.start)
        hi = bisect.bisect_left(opens, c.end)
        computes = [
            cut_compute(e, by_win.get(e.stats.get("win"), []), lane, caller,
                        opens[i + 1] if i + 1 < hi else c.end)
            for i, e in enumerate(spans[lo:hi], lo)]
        if not computes:
            continue
        if None in computes:
            unanchored += 1
            continue
        computes = [k._replace(nth=i) for i, k in enumerate(computes)]
        parts = {p: sum(k.parts[p] for k in computes) for p in PARTS}
        parts[OPEN] = computes[0].start - c.start
        calls.append(Call(c.start, c.end, computes, parts))
        every += computes
    if not calls:
        return None

    # -- the device's line: the scale first, then the shift ------------------
    ops = chip_ops(profile, chip)
    busy = Busy(ops) if len(ops) else None
    raw_scale, scale, found = None, 1.0, Causal()
    idle_ms, idle_parts = None, {}
    if busy is not None:
        anchor = next((k.called for k in every if k.called is not None),
                      every[0].site)
        host_extent = every[-1].landed - anchor
        # (a call left out leaves the two extents over different computes)
        if host_extent > 0 and not unanchored:
            raw_scale = float(busy.end[-1] - busy.start[0]) / host_extent
            if abs(raw_scale - 1.0) > SCALE_TOLERANCE:
                busy.scale = raw_scale
                busy.pivot_device = float(busy.start[0])
                busy.pivot_host = anchor
        found = causal(busy, every)
        if busy.scale != 1.0 and found.edges > 1:
            # the extents differ by a wake-up and a dispatch-to-start, which
            # is the slack itself: the scale near theirs that the edges agree
            # on most
            better = steadiest_scale(busy, every)
            if better is not None:
                busy.scale = better
                found = causal(busy, every)
        scale = busy.scale
        busy.shift = found.shift_s
        idle = [{p: sum(busy.idle_in(*k.stretches[p]) for k in c.computes)
                 for p in PARTS}
                | {OPEN: busy.idle_in(c.start, c.computes[0].start)}
                for c in calls]
        idle_parts = {p: 1e3 * statistics.median(i[p] for i in idle)
                      for p in (OPEN,) + PARTS}
        idle_ms = 1e3 * statistics.median(
            busy.idle_in(c.start, c.end) for c in calls)

    wall = statistics.median(c.wall_s for c in calls)
    slow = [c for c in calls if c.wall_s > SLOW * wall]
    others = [c for c in calls if c.wall_s <= SLOW * wall]
    parts_ms = _medians(calls)
    return CallEdge(
        chip=chip, lane=lane, calls=len(calls), computes=len(every),
        unanchored=unanchored, wall_ms=1e3 * wall, parts_ms=parts_ms,
        sum_ms=sum(parts_ms.values()),
        sum_gap_ms=1e3 * max(abs(sum(c.parts.values()) - c.wall_s)
                             for c in calls),
        middle_ms={p: 1e3 * v for p, v in sorted(
            calls, key=lambda c: c.wall_s)[len(calls) // 2].parts.items()},
        edge_ms=1e3 * statistics.median(c.edge_s for c in calls),
        upload_ms=1e3 * statistics.median(
            sum(k.upload_s for k in c.computes) for c in calls),
        after_ms=1e3 * statistics.median(
            sum(k.after_s for k in c.computes) for c in calls),
        raw_scale=raw_scale, scale=scale, edges=found.edges,
        lower_s=found.lower_s, upper_s=found.upper_s, shift_s=found.shift_s,
        unserved=found.unserved, worst=found.worst, idle_ms=idle_ms,
        idle_parts_ms=idle_parts, slow_calls=len(slow),
        slow_parts_ms=_medians(slow),
        other_parts_ms=_medians(others))


def of(ctx) -> CallEdge | None:
    """The run's reduction, made once and kept on ``ctx`` (the reader runs
    before ``run.py`` removes the trace)."""
    if not hasattr(ctx, "call_edge"):
        lanes = {w.device.id: w.index for w in ctx.cr.cores.workers}
        ctx.call_edge = e = reduce(
            xplane._profile(xplane.find_xplane(host_phases.TRACE_DIR)),
            ctx.reduced, lanes)
        if e is not None:
            print("[bench] call edge: " + report(e), flush=True)
    return ctx.call_edge


def report(e: CallEdge) -> str:
    def ms(seconds) -> str:
        return "none" if seconds is None else f"{1e3 * seconds:.3f}"

    def rounded(parts: dict, per: float = 1.0) -> dict:
        return {k: round(v / per, 3) for k, v in parts.items()}

    per = e.computes / e.calls
    grew = ""
    if e.slow_calls and e.other_parts_ms:
        part = max(PARTS, key=lambda p: e.slow_parts_ms[p]
                   - e.other_parts_ms[p])
        grew = (f", their parts {rounded(e.slow_parts_ms)} beside the "
                f"others' {rounded(e.other_parts_ms)}: `{part}` grew most, by "
                f"{e.slow_parts_ms[part] - e.other_parts_ms[part]:.3f} ms")
    if e.raw_scale is None:
        clock = "no device line"
    elif e.scale == 1.0:
        clock = (f"scale {e.scale:.5f} (the device's extent over the host's "
                 f"reads {e.raw_scale:.5f}: inside {SCALE_TOLERANCE} of 1, "
                 "the timeline as recorded)")
    else:
        clock = (f"scale {e.scale:.5f}: THE DEVICE CLOCK IS SCALED, every "
                 f"device duration reads {e.scale:.4f} x the host's (the "
                 f"extents' {e.raw_scale:.5f}, then the stretch near it under "
                 "which the edges' bounds differ least); the timeline "
                 "stretched by it about its first operation laid on the "
                 "first part:call")
    return (
        f"chip {e.chip} (lane {e.lane}), {e.calls} calls of {per:.2f} "
        f"computes ({e.unanchored} calls left out: a compute of theirs read "
        f"nothing back); ms a call on the host's clock alone, medians: "
        f"{rounded(e.parts_ms)}"
        + (", ms a compute " + str(rounded(
            {p: e.parts_ms[p] for p in PARTS}, per)) if per > 1 else "")
        + f"; every call's parts add up to its bench/call (largest "
        f"difference {e.sum_gap_ms:.6f} ms), the medians to {e.sum_ms:.3f} "
        f"against the median bench/call {e.wall_ms:.3f} (the call of that "
        f"wall alone: {rounded(e.middle_ms)}); inside `lane` the uploads {e.upload_ms:.3f}, "
        f"inside `run` the first launch's tail {e.after_ms:.3f}; "
        f"call_edge_ms_per_call {e.edge_ms:.3f} (everything outside `run`); "
        f"{clock}; {e.edges} edges (a compute's last part:landed to the "
        f"next one's first dispatch site), causal for shifts in "
        f"[{ms(e.lower_s)}, {ms(e.upper_s)}] ms, slack {ms(e.slack_s)} ms = "
        f"least wake-up + least dispatch-to-start, shifted by "
        f"{ms(e.shift_s)}"
        + (f" (NOT causal: {e.unserved} edges are left short, the worst "
           + ", ".join(f"{ms(v)} ms before compute {nth} of its call over a "
                       f"stretch of {ms(w)}" for v, nth, w in e.worst) + ")"
           if e.unserved else "")
        + "; the chip's idle a call "
        + ("none" if e.idle_ms is None else f"{e.idle_ms:.3f}")
        + f" put to the parts on that timeline {rounded(e.idle_parts_ms)}; "
        f"{e.slow_calls} calls over {SLOW} x the median wall" + grew)


def main(argv=None) -> int:
    """A traced run of ``--workload`` with ``call_edge_ms_per_call`` read
    whatever the cell lists (module docstring)."""
    import argparse

    import cells
    import run

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    name, load_cell = "call_edge_ms_per_call", cells.load_cell
    entry = next(m for m in cells.manifest()["per_layer"]
                 if m["name"] == name)

    def with_the_entry(workload: str):
        cell = load_cell(workload)
        listed = name in [m["name"] for m in cell.per_layer]
        return cell if listed else cell._replace(
            per_layer=cell.per_layer + [entry])

    cells.load_cell = with_the_entry
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "1"])


if __name__ == "__main__":
    raise SystemExit(main())
