"""Device dispatches the flush after a range move made to issue its
read-backs, per call that flushed, summed over the lanes: the ``dispatches``
field of the ``ck/resync`` instants tagged ``part:lane`` (one a lane where
``Cores._start_deferred_downloads`` ends its issue, with ``lane``,
``dispatches``, ``pieces``, ``bytes``, ``issue_us``) that lie inside the
window, over the flushes that hold them (the marks of one flush share their
``win``; a call whose ranges stood still has no flush and is not in the
denominator: the number says what ONE flush costs, however often the balancer
moves).  One batched slicing program a lane reads the number of lanes (4 in
``mandelbrot_balance_4chip``); the per-record path it replaced made one
dispatch a chunk of a record (5-9 a lane there).  A program without the mark
(a parent commit) leaves nothing to read."""

import host_phases
import xplane

RESYNC = host_phases.PREFIX + "resync"
LANE_MARK = "part:lane"


def dispatches(lines, t0: float, t1: float) -> tuple[int, int]:
    """``(dispatches, flushes)`` of the lane marks that start inside
    ``[t0, t1)``, over the host threads' spans (``host_phases.host_lines``)."""
    total, flushes = 0, set()
    for spans in lines:
        for s in spans:
            if (s.name == RESYNC and s.stats.get("tag") == LANE_MARK
                    and t0 <= s.start < t1):
                total += int(s.stats.get("dispatches", 0))
                flushes.add(s.stats.get("win"))
    return total, len(flushes)


def read(ctx):
    lines = host_phases.host_lines(
        xplane._profile(xplane.find_xplane(host_phases.TRACE_DIR)))
    total, flushes = dispatches(lines, ctx.reduced.t0, ctx.reduced.t1)
    return total / flushes if flushes else None
