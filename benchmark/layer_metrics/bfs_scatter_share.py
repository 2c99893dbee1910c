"""Share of the levels' device time spent in scatter operations, in percent,
over the levels' time (``bfs_kernel_ms_per_level``'s).

What a scatter looks like in a v5e trace (read off this cell's, PR 40): as
a gather does (``spmv_gather_share``), ``.at[].set`` comes out as a custom
fusion whose name says nothing, ``%fusion.26 = s8[1000192] fusion(s8[1000192]
%table, s32[524288] %indices, s8[524288] %updates), kind=kCustom``: what tells
it from a gather (``%fusion.24 = s8[524288] fusion(s8[1000192] %table,
s32[524288] %indices), kind=kCustom``) is that it takes the table, the indices
AND the updates and gives back the table's shape.  The scatter of a ``char``
table is preceded by a ``sort`` of its indices and updates, which no kernel
statement asks for: the sort counts as the scatter's.  So an operation is a
scatter when its opcode is ``scatter`` or ``sort``, or it is a ``kind=kCustom``
fusion of three or more operands whose result has the first operand's type
and shape.  The shared test of ``bfs_gather_share``, which counts the gathers
that are no scatter (the opcode ``gather`` or any other ``kind=kCustom``
fusion)."""

import re

import cells
import xplane

_gathers = cells.load_reader("spmv_gather_share")
_TYPED = re.compile(r"\b([a-z]+[0-9]*\[[0-9,]*\])")


def is_scatter(text: str) -> bool:
    """From an event's HLO text."""
    _name, opcode = xplane.op_label(text)
    if opcode in ("scatter", "sort"):
        return True
    if opcode != "fusion" or _gathers.CUSTOM_FUSION not in text:
        return False
    head, _sep, operands = text.partition(" fusion(")
    result = _TYPED.findall(head)
    taken = _TYPED.findall(operands.partition(" kind=")[0])
    return bool(result) and len(taken) >= 3 and taken[0] == result[0]


def is_gather(text: str) -> bool:
    return not is_scatter(text) and _gathers.is_gather(text)


def share(ctx, test) -> float | None:
    seconds, _levels = cells.load_reader(
        "bfs_kernel_ms_per_level").kernel_seconds(ctx)
    if not seconds:
        return None
    r = ctx.reduced
    import host_phases  # where run.py records the trace

    events = _gathers.chip_events(
        xplane._profile(xplane.find_xplane(host_phases.TRACE_DIR)),
        cells.load_reader("spmv_kernel_ms_per_iter").chip_of(r))
    inside = sum(max(0.0, min(end, r.t1) - max(start, r.t0))
                 for text, start, end in events if test(text))
    return 100.0 * inside / seconds


def read(ctx):
    return share(ctx, is_scatter)
