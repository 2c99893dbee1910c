"""The reduction's share of its roofline: the least time the chip could take
for the computes the window's calls ran, the bytes the WORK has to move (the
configuration's ``kernel_cost`` of each call's own ``n``: every element read
once, one partial a group written) over the peak HBM bytes/s, over the device
time of those computes (``reduce_kernel_ms_per_call``'s).  Bounded by memory:
one add an element."""

import cells


def least_bytes(ctx, computes: int) -> float:
    """The window's computes are the log's entries before the fresh call's."""
    log = ctx.data["sums"][-1 - computes:-1]
    return float(sum(ctx.cell.ref.kernel_cost(ctx.cfg, ctx.params, ctx.n, n=n)
                     ["bytes"] for n, _sum in log))


def read(ctx):
    seconds, computes = cells.load_reader(
        "reduce_kernel_ms_per_call").kernel_seconds(ctx)
    if not computes or not seconds:
        return None
    return (100.0 * least_bytes(ctx, computes)
            / ctx.peaks["hbm_bytes_per_s"] / seconds)
