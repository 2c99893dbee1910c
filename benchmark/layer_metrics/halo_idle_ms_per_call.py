"""Worst-chip idle per call while the program fetched, for a compute whose
kernels read across lanes, the rows their neighbours wrote: the idle that
``host_phases`` puts down to ``ck/halo`` (``Cores._stage_exchange``: the
strips are cut and sent on the caller's thread, before the lanes' launches
of the compute).  ``host_phases`` names no group for the kind, so
``dispatch_idle_ms_per_call`` counts the same gaps among its own.  A program
whose trace holds no such span (a parent commit, a cell without an exchange)
leaves nothing to read."""

import host_phases
import xplane

HALO = host_phases.PREFIX + "halo"


def halo_spans(ctx) -> list:
    """The ``ck/halo`` spans that start inside the traced window, of every
    lane (``host_phases.HostSpan``); read once, kept on ``ctx`` for the
    three readers."""
    if not hasattr(ctx, "halo_spans"):
        lines = host_phases.host_lines(
            xplane._profile(xplane.find_xplane(host_phases.TRACE_DIR)))
        ctx.halo_spans = in_window(lines, ctx.reduced.t0, ctx.reduced.t1)
    return ctx.halo_spans


def in_window(lines, t0: float, t1: float) -> list:
    return [s for spans in lines for s in spans
            if s.name == HALO and t0 <= s.start < t1]


def read(ctx):
    p = host_phases.of(ctx)
    if p is None or not halo_spans(ctx):
        return None
    return 1e3 * p.by_kind.get(HALO, 0.0) / p.calls
