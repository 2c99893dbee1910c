"""The product's share of its roofline: the least time the chip could take
for one product — the larger of operations over the peak flop/s and the
LEAST bytes over the peak HBM bytes/s, both from the configuration's own
shape function (``col`` and ``val`` once, ``rowptr`` once, each of ``x`` and
``y`` once) — over the kernel's device time a product.  Bounded by memory:
2 flop against 8 bytes a stored nonzero."""

import cells


def read(ctx):
    seconds, products = cells.load_reader(
        "spmv_kernel_ms_per_iter").kernel_seconds(ctx)
    if not products or not seconds:
        return None
    cost = ctx.cell.ref.kernel_cost(ctx.cfg, ctx.params, ctx.n)
    least = max(cost["ops"] / ctx.peaks["flops_per_s"],
                cost["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * products / seconds
