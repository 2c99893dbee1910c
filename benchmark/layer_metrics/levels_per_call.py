"""Levels a traversal ran, per call: the ``ck/launch`` spans of the worst
chip's lane inside each ``bench/call`` span of the traced window (a level is
one synchronous ``compute()`` of both kernels, one ``Worker.launch``; the
call's last compute, which only brings ``cost`` back, launches nothing).
Rodinia's graph1MW_6 from the configuration's sources: 12.

The shared reduction of the readers that go by a traversal's levels
(``bfs_kernel_ms_per_level``, ``flag_roundtrip_ms_per_level``,
``upload_bytes_per_call``, ``scattered_accesses``): a call here is a
``bench/call`` span, not a ``win`` (every compute of a traversal opens a
window id of its own).  No device line is read; a program without the spans
leaves nothing to read.  ``checks/test_bfs_cell.py`` holds it to a trace made
by hand."""

from typing import NamedTuple

import host_phases
import xplane

CALL = "bench/call"
LAUNCH, UPLOAD, DOWNLOAD = (host_phases.PREFIX + k
                            for k in ("launch", "upload", "download"))
FLAG = "over"  # the array the kernels raise for the host


class Traversals(NamedTuple):
    calls: int           # bench/call spans inside the window
    levels: int          # the lane's ck/launch spans inside them
    upload_bytes: float  # the ``bytes`` of the lane's ck/upload spans there
    flag_up_s: float     # the flag's ck/upload spans, summed
    flag_down_s: float   # the flag's ck/download spans, summed
    flag_moves: int      # those spans, counted
    access: str | None   # the ``access`` field of the first call's launches


def reduce(lines, t0: float, t1: float, lane: int) -> Traversals | None:
    """``lines``: ``host_phases.host_lines``."""
    calls = [s for spans in lines for s in spans
             if s.name == CALL and t0 <= s.start and s.end <= t1]
    mine = sorted((s for spans in lines for s in spans
                   if s.name in (LAUNCH, UPLOAD, DOWNLOAD)
                   and s.stats.get("lane") == lane and s.end > s.start),
                  key=lambda s: s.start)
    launches = [s for s in mine if s.name == LAUNCH]
    if not calls or not launches:
        return None
    levels = moves = 0
    nbytes = up_s = down_s = 0.0
    access = None
    for call in sorted(calls, key=lambda s: s.start):
        for s in mine:
            if not call.start <= s.start < call.end:
                continue
            if s.name == LAUNCH:
                levels += 1
                access = access or s.stats.get("access")
            elif s.name == UPLOAD:
                nbytes += float(s.stats.get("bytes", 0))
            if s.name != LAUNCH and str(s.stats.get("tag")) == FLAG:
                moves += 1
                if s.name == UPLOAD:
                    up_s += s.end - s.start
                else:
                    down_s += s.end - s.start
    return Traversals(len(calls), levels, nbytes, up_s, down_s, moves, access)


def of(ctx) -> Traversals | None:
    """The run's reduction, made once and kept on ``ctx`` (the readers run
    before ``run.py`` removes the trace)."""
    if not hasattr(ctx, "traversals"):
        p = host_phases.of(ctx)  # the worst chip's lane
        ctx.traversals = r = None if p is None else reduce(
            host_phases.host_lines(
                xplane._profile(xplane.find_xplane(host_phases.TRACE_DIR))),
            ctx.reduced.t0, ctx.reduced.t1, p.lane)
        if r is not None and r.levels:
            print(f"[bench] traversals: {r.calls} calls, "
                  f"{r.levels / r.calls:.2f} levels a call, "
                  f"{r.upload_bytes / r.calls:.0f} bytes up a call; the flag "
                  f"a level: upload {1e3 * r.flag_up_s / r.levels:.3f} ms, "
                  f"download {1e3 * r.flag_down_s / r.levels:.3f} ms (the "
                  "wait for the level's kernels included); access "
                  f"{r.access}", flush=True)
    return ctx.traversals


def read(ctx):
    r = of(ctx)
    return None if r is None else r.levels / r.calls
