"""Buffer reads of the launched kernel that were lowered to a per-lane
gather, as the program counted them when it built it: the ``gather`` count of
the ``access`` field on the lane's ``ck/launch`` spans
(``access=slice:0;strided:0;uniform:0;gather:0;scatter:1;carried:0;local:7;
group:2``), read off the lane's first launch of the traced window (every call
runs the same launcher), parsed by ``mvt_gathered_accesses.parse``.  SHOC's
``reduce`` has two reads of ``g_idata`` in its walk: 2 where each fetches a
row of 128 a work item, 0 where a group fetches its window
(``group_slice_accesses``, which shares this reduction).  A program whose
spans carry no such field leaves nothing to read."""

import cells
import host_phases
import xplane

LAUNCH = host_phases.PREFIX + "launch"


def access_field(lines, t0: float, t1: float, lane: int):
    """Over the host threads' spans (``host_phases.host_lines``): the parsed
    ``access`` field of the lane's first launch inside the window that
    carries one; None where none does."""
    spans = sorted((s for line in lines for s in line
                    if s.name == LAUNCH and s.stats.get("lane") == lane
                    and t0 <= s.start < t1 and "access" in s.stats),
                   key=lambda s: s.start)
    if not spans:
        return None
    return cells.load_reader("mvt_gathered_accesses").parse(
        spans[0].stats["access"])


def of(ctx):
    """The run's reduction, made once and kept on ``ctx`` for both readers
    (they run before ``run.py`` removes the trace)."""
    if not hasattr(ctx, "access_field"):
        p = host_phases.of(ctx)  # the worst chip's lane
        ctx.access_field = None if p is None else access_field(
            host_phases.host_lines(
                xplane._profile(xplane.find_xplane(host_phases.TRACE_DIR))),
            ctx.reduced.t0, ctx.reduced.t1, p.lane)
        if ctx.access_field is not None:
            print(f"[bench] access: {ctx.access_field}", flush=True)
    return ctx.access_field


def read(ctx):
    f = of(ctx)
    return None if f is None or "gather" not in f else float(f["gather"])
