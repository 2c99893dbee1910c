"""Accesses of the launched kernel's ``__global floatN*`` parameters, as the
program counted them when it built the kernel: ``loads + gathers + stores`` of
the ``vector`` field on the lane's ``ck/launch`` spans
(``vector=params:2;width:4;loads:1;gathers:1;stores:1``: what was BUILT, one
count an access whatever its width), read off the lane's first launch of the
traced window that carries one (every call runs the same launcher).  SHOC's
``compute_lj_force`` has three: its own position (a slice), a neighbour's (one
row gather a vector) and the force's store (a slice).  A kernel written out
component by component has no such parameter and no field; more than three
here says an access of a vector was split.  A program whose spans carry no
such field (a parent commit) leaves nothing to read."""

import cells
import host_phases
import xplane

LAUNCH = host_phases.PREFIX + "launch"


def vector_field(lines, t0: float, t1: float, lane: int):
    """Over the host threads' spans (``host_phases.host_lines``): the parsed
    ``vector`` field of the lane's first launch inside the window that carries
    one; None where none does."""
    spans = sorted((s for line in lines for s in line
                    if s.name == LAUNCH and s.stats.get("lane") == lane
                    and t0 <= s.start < t1 and "vector" in s.stats),
                   key=lambda s: s.start)
    if not spans:
        return None
    # ``"params:2;width:4;loads:1;.."`` -> ``{"params": 2, "width": 4, ..}``
    # (by name: of widths ``2+4`` the first, which no reader asks for)
    return cells.load_reader("group_barriers_per_launch").parse(
        spans[0].stats["vector"])


def of(ctx):
    """The run's reduction, made once and kept on ``ctx`` (the readers run
    before ``run.py`` removes the trace)."""
    if not hasattr(ctx, "vector_field"):
        p = host_phases.of(ctx)  # the worst chip's lane
        ctx.vector_field = None if p is None else vector_field(
            host_phases.host_lines(
                xplane._profile(xplane.find_xplane(host_phases.TRACE_DIR))),
            ctx.reduced.t0, ctx.reduced.t1, p.lane)
        if ctx.vector_field is not None:
            print(f"[bench] vector: {ctx.vector_field}", flush=True)
    return ctx.vector_field


def read(ctx):
    f = of(ctx)
    if f is None or not {"loads", "gathers", "stores"} <= set(f):
        return None
    return float(f["loads"] + f["gathers"] + f["stores"])
