"""Barrier statements the launched kernel holds, as the program counted them
when it built it: the ``barriers`` of the ``local`` field on the lane's
``ck/launch`` spans (``local=arrays:1;bytes:1024;barriers:2;sites:shift:6,
uniform:1,row:0``; a profiler annotation carries the commas as ``;``), read
off the first call of the traced window.  Each lowers to nothing (every
statement has run for all lanes of a launch before the next starts); SHOC's
``reduce`` has two, the one behind the walk and the one in the tree's loop,
which its eight passes reach.  The shared reduction of
``local_row_accesses``.  A program whose spans carry no such field (a parent
commit) leaves nothing to read."""

import re

import host_phases
import xplane

LAUNCH = host_phases.PREFIX + "launch"


def parse(field: str) -> dict:
    """``"arrays:1;bytes:1024;barriers:2;sites:shift:6,uniform:1,row:0"`` (or
    with ``;`` for the commas) -> ``{"arrays": 1, ..., "row": 0}``."""
    return {k: int(v) for k, v in re.findall(r"(\w+):(\d+)", str(field))}


def local_field(lines, t0: float, t1: float, lane: int):
    """Over the host threads' spans (``host_phases.host_lines``): the parsed
    ``local`` field of the lane's first launch inside the window that carries
    one; None where none does."""
    spans = sorted((s for line in lines for s in line
                    if s.name == LAUNCH and s.stats.get("lane") == lane
                    and t0 <= s.start < t1 and "local" in s.stats),
                   key=lambda s: s.start)
    return parse(spans[0].stats["local"]) if spans else None


def of(ctx):
    """The run's reduction, made once and kept on ``ctx`` for both readers
    (they run before ``run.py`` removes the trace)."""
    if not hasattr(ctx, "local_field"):
        p = host_phases.of(ctx)  # the worst chip's lane
        ctx.local_field = None if p is None else local_field(
            host_phases.host_lines(
                xplane._profile(xplane.find_xplane(host_phases.TRACE_DIR))),
            ctx.reduced.t0, ctx.reduced.t1, p.lane)
        if ctx.local_field is not None:
            print(f"[bench] local memory: {ctx.local_field}", flush=True)
    return ctx.local_field


def read(ctx):
    f = of(ctx)
    return None if f is None or "barriers" not in f else float(f["barriers"])
