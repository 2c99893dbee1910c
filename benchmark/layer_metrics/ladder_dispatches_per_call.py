"""Dispatches the per-call launches of the worst chip's lane made, per call:
the ``xN`` at the end of the tag of every ``ck/launch`` span of that lane that
started inside the window, summed, those of the fused window dispatches
(tag ``fused:...``, where ``xN`` counts iterations) left out.  ``N`` is what
``Worker.launch`` counted: one per rung of the lane's launch ladder when the
host walks it, 1 when the launch rode the fused ladder executable.  The lane
and the window are ``host_phases``'s; a program without the spans leaves the
metric out."""

import re

import host_phases
import xplane

LAUNCH = host_phases.PREFIX + "launch"
_XN = re.compile(r" x(\d+)$")


def dispatches(lines, t0: float, t1: float, lane: int) -> int:
    """Summed over the host threads' spans (``host_phases.host_lines``)."""
    total = 0
    for spans in lines:
        for s in spans:
            if (s.name != LAUNCH or s.stats.get("lane") != lane
                    or not t0 <= s.start < t1):
                continue
            tag = str(s.stats.get("tag", ""))
            m = _XN.search(tag)
            if m is not None and not tag.startswith("fused:"):
                total += int(m.group(1))
    return total


def read(ctx):
    p = host_phases.of(ctx)
    if p is None:
        return None
    lines = host_phases.host_lines(
        xplane._profile(xplane.find_xplane(host_phases.TRACE_DIR)))
    return dispatches(lines, ctx.reduced.t0, ctx.reduced.t1, p.lane) / p.calls
