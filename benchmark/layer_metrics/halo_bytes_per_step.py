"""Bytes the lanes fetched from one another a step: the ``bytes`` of the
``ck/halo`` spans of ALL lanes inside the traced window, over the computes
its calls ran.  In a steady window that is the reach alone: on four lanes
six strips a step (two for each inner lane, one for each outer) of
``width`` float32 cells; a range the balancer moved adds the gained strip
once.  A program without the span leaves nothing to read."""

import cells


def bytes_per_step(spans, computes: int):
    if not spans or not computes:
        return None
    return sum(float(s.stats.get("bytes", 0)) for s in spans) / computes


def read(ctx):
    spans = cells.load_reader("halo_idle_ms_per_call").halo_spans(ctx)
    return bytes_per_step(
        spans, ctx.reduced.calls * int(ctx.params["iterations_per_call"]))
