"""Buffer accesses of the compute's kernels that were lowered to a per-lane
gather or a scatter, as the program counted them when it built the kernels:
the ``gather`` and ``scatter`` counts of the ``access`` field that the
``ck/launch`` / ``ck/fused`` spans of the worst chip's lane carry
(``access=slice:2;strided:1;uniform:2;gather:0;scatter:0;carried:2``), read
off the first call of the traced window (every call runs the same
launchers).  A program whose spans carry no such field (a parent commit)
leaves nothing to read.  0 where every access is a slice, a strided window,
a scalar or a carried local."""

import host_phases
import xplane

KINDS = tuple(host_phases.PREFIX + k for k in ("launch", "fused"))
COUNTED = ("gather", "scatter")


def parse(field: str) -> dict:
    """``"slice:2;gather:0"`` -> ``{"slice": 2, "gather": 0}``."""
    pairs = (item.partition(":") for item in str(field).split(";") if item)
    return {k: int(v) for k, _sep, v in pairs}


def gathered(lines, t0: float, t1: float, lane: int):
    """Over the host threads' spans (``host_phases.host_lines``): the most
    ``gather`` + ``scatter`` any launch span of the lane inside the window's
    first call names; None where none carries the field."""
    spans = sorted((s for line in lines for s in line
                    if s.name in KINDS and s.stats.get("lane") == lane
                    and t0 <= s.start < t1 and "access" in s.stats),
                   key=lambda s: s.start)
    if not spans:
        return None
    first = [s for s in spans if s.stats.get("win") == spans[0].stats.get("win")]
    return max(sum(parse(s.stats["access"]).get(k, 0) for k in COUNTED)
               for s in first)


def read(ctx):
    p = host_phases.of(ctx)
    if p is None:
        return None
    lines = host_phases.host_lines(
        xplane._profile(xplane.find_xplane(host_phases.TRACE_DIR)))
    got = gathered(lines, ctx.reduced.t0, ctx.reduced.t1, p.lane)
    return None if got is None else float(got)
