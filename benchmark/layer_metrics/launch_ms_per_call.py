"""The host's time inside ``Worker.launch`` on the HOST's clock alone, per
call: the lengths of the ``ck/launch`` spans of the worst chip's lane, summed
over each call (one ``win``) and averaged over the calls whose launches lie
inside the traced window.  A span holds what the host does to hand the
runtime a launch's dispatches: looking up the launcher, handing over the
run-time scalars (a host-to-device transfer each for a Python number, one for
a packed vector), the dispatch itself; not the kernel, which is dispatched
asynchronously.  In ``mandelbrot_percall_1chip`` a call is four launches, one
a chunk of the streamed read-back.  No device line is read, and it reads on
any program that has the span (a parent commit too).

The shared reduction of ``loose_scalars_per_call``: since ISSUE 39 the span
carries ``scalars=packed:W;loose:L`` (``registry.lowering_meta``): the 32-bit
words of run-time scalars its dispatches handed over in one vector each, and
the Python / numpy scalars that crossed one by one.  A program without the
field leaves ``packed`` and ``loose`` None.
``checks/test_launch_scalars.py`` holds both to a trace made by hand."""

from typing import NamedTuple

import cells
import host_phases
import xplane

LAUNCH = host_phases.PREFIX + "launch"


class Launches(NamedTuple):
    calls: int            # calls (``win``) whose launches lie in the window
    spans: int            # their ``ck/launch`` spans
    launch_s: float       # the spans' lengths, summed
    packed: int | None    # the ``scalars`` fields' words, summed; None where
    loose: int | None     # no span carries the field

    def per_call(self, total):
        return None if total is None else total / self.calls


def reduce(lines, t0: float, t1: float, lane: int) -> Launches | None:
    """``lines``: ``host_phases.host_lines``.  The lane's ``ck/launch`` spans
    grouped by ``win``; a call counts if every one of them lies in [t0, t1]."""
    calls: dict = {}
    for spans in lines:
        for s in spans:
            if (s.name == LAUNCH and s.stats.get("lane") == lane
                    and "win" in s.stats and s.end > s.start):
                calls.setdefault(s.stats["win"], []).append(s)
    inside = [spans for spans in calls.values()
              if all(t0 <= s.start and s.end <= t1 for s in spans)]
    if not inside:
        return None
    ours = [s for spans in inside for s in spans]
    # ``"packed:7;loose:0"`` -> ``{"packed": 7, "loose": 0}``
    parse = cells.load_reader("mvt_gathered_accesses").parse
    fields = [parse(s.stats["scalars"]) for s in ours if "scalars" in s.stats]
    return Launches(
        len(inside), len(ours), sum(s.end - s.start for s in ours),
        sum(f.get("packed", 0) for f in fields) if fields else None,
        sum(f.get("loose", 0) for f in fields) if fields else None)


def of(ctx) -> Launches | None:
    """The run's reduction, made once and kept on ``ctx`` for both readers
    (they run before ``run.py`` removes the trace)."""
    if not hasattr(ctx, "launches"):
        p = host_phases.of(ctx)  # the worst chip's lane
        ctx.launches = r = None if p is None else reduce(
            host_phases.host_lines(
                xplane._profile(xplane.find_xplane(host_phases.TRACE_DIR))),
            ctx.reduced.t0, ctx.reduced.t1, p.lane)
        if r is not None:
            print(f"[bench] launches: {r.calls} calls, "
                  f"{r.spans / r.calls:.2f} ck/launch spans a call, "
                  f"{1e3 * r.launch_s / r.calls:.3f} ms a call inside them "
                  f"({1e3 * r.launch_s / r.spans:.3f} ms a span); run-time "
                  "scalars a call: "
                  + ("no field (a program before ISSUE 39)" if r.loose is None
                     else f"{r.per_call(r.packed):.2f} words packed, "
                          f"{r.per_call(r.loose):.2f} loose"), flush=True)
    return ctx.launches


def read(ctx):
    r = of(ctx)
    return None if r is None else 1e3 * r.launch_s / r.calls
