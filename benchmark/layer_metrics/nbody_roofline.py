"""The n-body kernel's share of its roofline: the least time the chip could
take for the launches of the traced window — the larger of operations over
the peak flop/s and bytes over the peak bytes/s, both from the
configuration's own shape function, counted per launch — over the kernel's
device time.  Bounded by compute: 18 flop a pair against 24 bytes a body.
The only published compute peak is the bf16 matrix peak, and this kernel is
float32 vector work, so the share is small by construction."""

import xplane


def read(ctx):
    r = ctx.reduced
    chip = next(iter(r.busy_s))
    seconds, launches = xplane.seconds_of(r, chip, ctx.cfg["kernel_opcode"])
    if not launches:
        return None
    iters = r.calls * int(ctx.params["iterations_per_call"])
    cost = ctx.cell.ref.kernel_cost(ctx.cfg, ctx.params,
                                    ctx.n * iters // launches)
    least = max(cost["ops"] / ctx.peaks["flops_per_s"],
                cost["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * launches / seconds
