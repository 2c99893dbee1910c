"""Share of the product's device time spent gathering, in percent: the
chip's gather operations inside the window over the kernel's time
(``spmv_kernel_ms_per_iter``'s).

What a gather looks like in a v5e trace (read off this cell's, PR 26): XLA's
TPU backend wraps a gather and the arithmetic on its indices in a custom
fusion, and the event's HLO text reads ``%fusion.1 = s32[16777216] fusion(
s32[449455096] %table, s32[16777216] %indices), kind=kCustom, calls=...``:
the name says nothing (``fusion``, ``fusion.1``), the kind does, where loop
fusions read ``kind=kLoop`` and carry their operations in their names.  So an
operation counts as a gather when its opcode is ``gather``, or it is a
``fusion`` of ``kind=kCustom``.  ``xplane.Reduced`` keeps names and opcodes
only, so this reader goes through the trace's events itself."""

import cells
import xplane

CUSTOM_FUSION = "kind=kCustom"


def is_gather(text: str) -> bool:
    """From an event's HLO text."""
    _name, opcode = xplane.op_label(text)
    return opcode == "gather" or (opcode == "fusion" and CUSTOM_FUSION in text)


def gather_seconds(events, t0: float, t1: float) -> float:
    """``events``: (HLO text, start, end) of one chip's operations; the
    gathers' time, clipped to the window."""
    return sum(max(0.0, min(end, t1) - max(start, t0))
               for text, start, end in events if is_gather(text))


def chip_events(profile, chip: int) -> list:
    out = []
    for plane in profile.planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        if m is None or int(m.group(1)) != chip:
            continue
        for line in plane.lines:
            if line.name == xplane.OPS_LINE:
                out += [(ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
    return out


def read(ctx):
    kernel = cells.load_reader("spmv_kernel_ms_per_iter")
    seconds, _products = kernel.kernel_seconds(ctx)
    if not seconds:
        return None
    r = ctx.reduced
    import host_phases  # where run.py records the trace

    events = chip_events(
        xplane._profile(xplane.find_xplane(host_phases.TRACE_DIR)),
        kernel.chip_of(r))
    return 100.0 * gather_seconds(events, r.t0, r.t1) / seconds
