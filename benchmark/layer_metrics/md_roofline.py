"""The force step's share of its roofline: the least time the chip could take
for the computes the window's calls ran, the bytes the ALGORITHM has to move
(the configuration's ``kernel_cost``: every entry of the neighbour list once,
ONE 16-byte position a neighbour, an atom's own position and its force) over
the peak HBM bytes/s, over the device time of those computes
(``md_kernel_ms_per_call``'s).  Bounded by memory: 22 floating-point
operations to 20 bytes a pair.  The bytes are the work's whatever lowers it: a
gather that fetches a 512-byte row for a 16-byte position moves 32 times the
counted bytes and reads a small share here."""

import cells


def read(ctx):
    seconds, computes = cells.load_reader(
        "md_kernel_ms_per_call").kernel_seconds(ctx)
    if not computes or not seconds:
        return None
    least = ctx.cell.ref.kernel_cost(ctx.cfg, ctx.params, ctx.n)["bytes"]
    return 100.0 * computes * least / ctx.peaks["hbm_bytes_per_s"] / seconds
