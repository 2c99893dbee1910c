"""Device time of one compute (both kernels: the row walk and the column
walk), from the trace: the time of ALL the chip's operations inside the
window but the containers (``while``, ``conditional``, ``call``: their
bodies are counted), copies INCLUDED, over the computes the window's calls
ran.  Nothing else runs on this chip in the window, and a lowering that
moved its work into copies (a transposition, a view made anew every launch)
must not hide it there (PERF.md section 7, row 13)."""

import cells
import xplane


def kernel_seconds(ctx) -> tuple[float, int]:
    """(seconds of the chip's operations, computes) in the window."""
    r = ctx.reduced
    chip = cells.load_reader("spmv_kernel_ms_per_iter").chip_of(r)
    seconds = sum(v for (_name, opcode), v in r.op_seconds[chip].items()
                  if opcode not in xplane.CONTAINERS)
    return seconds, r.calls * int(ctx.params["iterations_per_call"])


def read(ctx):
    seconds, computes = kernel_seconds(ctx)
    return 1e3 * seconds / computes if computes and seconds else None
