"""The compute's share of its roofline: the least time the chip could take
for one compute (both kernels) — the larger of operations over the peak
flop/s and the LEAST bytes over the peak HBM bytes/s, both from the
configuration's own shape function (``a`` once a kernel, each vector once a
kernel that touches it) — over the device time a compute
(``mvt_kernel_ms_per_iter``'s, copies included).  Bounded by memory: 2 flop
against 4 bytes an element of the matrix."""

import cells


def read(ctx):
    seconds, computes = cells.load_reader(
        "mvt_kernel_ms_per_iter").kernel_seconds(ctx)
    if not computes or not seconds:
        return None
    cost = ctx.cell.ref.kernel_cost(ctx.cfg, ctx.params, ctx.n)
    least = max(cost["ops"] / ctx.peaks["flops_per_s"],
                cost["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * computes / seconds
