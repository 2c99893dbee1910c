"""Device time of one level (``BFS_1`` and ``BFS_2`` over the whole range),
from the trace: the time of ALL the chip's operations inside the window but
the containers (``while``, ``conditional``, ``call``: their bodies are
counted), copies included, over the levels the window's calls ran
(``levels_per_call``'s: the lane's ``ck/launch`` spans).  Nothing else runs
on this chip in the window but the calls' uploads and the read-back of
``cost``, which are no operations of the device's ``XLA Ops`` line."""

import cells
import xplane


def kernel_seconds(ctx) -> tuple[float, int]:
    """(seconds of the chip's operations, levels) in the window."""
    r = ctx.reduced
    t = cells.load_reader("levels_per_call").of(ctx)
    chip = cells.load_reader("spmv_kernel_ms_per_iter").chip_of(r)
    seconds = sum(v for (_name, opcode), v in r.op_seconds[chip].items()
                  if opcode not in xplane.CONTAINERS)
    return seconds, 0 if t is None else t.levels


def read(ctx):
    seconds, levels = kernel_seconds(ctx)
    return 1e3 * seconds / levels if levels and seconds else None
