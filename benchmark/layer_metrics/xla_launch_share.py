"""Share of the lane's launches inside the traced window that ran the
vectorized-XLA lowering, in percent: the ``ck/launch`` spans of the worst
chip's lane whose ``lowering`` reads ``xla``, over all of them.  The program
writes the lowering its launcher was built with on every launch span; one
without the field (a parent commit) leaves nothing to read.  100 where every
launch fell to ``kernel/codegen.py``; a Pallas path for per-lane gathers
would move it."""

import host_phases
import xplane

LAUNCH = host_phases.PREFIX + "launch"


def share(lines, t0: float, t1: float, lane: int):
    """Over the host threads' spans (``host_phases.host_lines``); None where
    no launch of the lane inside the window names its lowering."""
    launches = [s for spans in lines for s in spans
                if s.name == LAUNCH and s.stats.get("lane") == lane
                and t0 <= s.start < t1]
    if not any("lowering" in s.stats for s in launches):
        return None
    return 100.0 * sum(s.stats.get("lowering") == "xla"
                       for s in launches) / len(launches)


def read(ctx):
    p = host_phases.of(ctx)
    if p is None:
        return None
    lines = host_phases.host_lines(
        xplane._profile(xplane.find_xplane(host_phases.TRACE_DIR)))
    return share(lines, ctx.reduced.t0, ctx.reduced.t1, p.lane)
