"""Share of the compute's device time spent in per-lane memory operations,
in percent: the chip's ``gather`` and ``scatter`` operations and its
``kind=kCustom`` fusions (how a v5e trace shows a gather with the arithmetic
on its indices: ``spmv_gather_share``'s test, with ``scatter`` added) inside
the window, over the compute's time (``mvt_kernel_ms_per_iter``'s).  0 where
every access of the two kernels is a slice."""

import cells
import xplane

_gathers = cells.load_reader("spmv_gather_share")
chip_events = _gathers.chip_events


def is_per_lane(text: str) -> bool:
    """From an event's HLO text."""
    _name, opcode = xplane.op_label(text)
    return opcode == "scatter" or _gathers.is_gather(text)


def per_lane_seconds(events, t0: float, t1: float) -> float:
    """``events``: (HLO text, start, end) of one chip's operations; the
    gathers' and scatters' time, clipped to the window."""
    return sum(max(0.0, min(end, t1) - max(start, t0))
               for text, start, end in events if is_per_lane(text))


def read(ctx):
    seconds, _computes = cells.load_reader(
        "mvt_kernel_ms_per_iter").kernel_seconds(ctx)
    if not seconds:
        return None
    r = ctx.reduced
    import host_phases  # where run.py records the trace

    events = chip_events(
        xplane._profile(xplane.find_xplane(host_phases.TRACE_DIR)),
        cells.load_reader("spmv_kernel_ms_per_iter").chip_of(r))
    return 100.0 * per_lane_seconds(events, r.t0, r.t1) / seconds
