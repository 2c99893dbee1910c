"""Share of the force step's device time spent gathering, in percent, over
the step's time (``md_kernel_ms_per_call``'s: all of the chip's operations,
copies included).  A gather of a vector is TWO costs on this chip, and both
are counted: the row fetch (``spmv_gather_share`` says what a gather looks
like in a v5e trace, and finds them: one 512-byte row a neighbour) and the
picks, the operations that read the fetched rows and keep N components of
each (``kernel/vectors.py``'s ``_row_gather`` makes both; in the trace a pick
is an operation with a gather's result among its operands).  Work that moves
from the one to the other leaves the share where it was."""

import re

import cells
import xplane

_NAME = re.compile(r"%([^\s,()]+)")


def operands(text: str) -> set:
    """The names an event's HLO text reads: every ``%name`` between its own
    and the attributes (``kind=``, ``calls=``)."""
    head = text.split(", calls=")[0].split(", kind=")[0]
    return set(_NAME.findall(head)[1:])


def gather_and_pick_seconds(events, t0: float, t1: float) -> float:
    """``events``: (HLO text, start, end) of one chip's operations; the time
    of the gathers and of the operations that read a gather's result (no
    container: its body's operations are events of their own), clipped to
    the window."""
    is_gather = cells.load_reader("spmv_gather_share").is_gather
    gathers = {xplane.op_label(text)[0] for text, _s, _e in events
               if is_gather(text)}
    return sum(max(0.0, min(end, t1) - max(start, t0))
               for text, start, end in events
               if is_gather(text) or (
                   xplane.op_label(text)[1] not in xplane.CONTAINERS
                   and operands(text) & gathers))


def read(ctx):
    seconds, _computes = cells.load_reader(
        "md_kernel_ms_per_call").kernel_seconds(ctx)
    if not seconds:
        return None
    import host_phases  # where run.py records the trace

    r = ctx.reduced
    events = cells.load_reader("spmv_gather_share").chip_events(
        xplane._profile(xplane.find_xplane(host_phases.TRACE_DIR)),
        cells.load_reader("spmv_kernel_ms_per_iter").chip_of(r))
    return 100.0 * gather_and_pick_seconds(events, r.t0, r.t1) / seconds
