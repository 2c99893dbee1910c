"""The step's share of its roofline on the chip that worked longest: the
least time that chip could take for its lane's share of one step (the larger
of operations over the peak flop/s and the LEAST bytes over the peak HBM
bytes/s, both from the configuration's shape function: seven passes over the
share) over the device time a step (``wave_kernel_ms_per_iter``'s).  The
lane's share is the mean of its range over the window's calls
(``ranges_log``).  Bounded by memory: 9 flop against 28 bytes a cell."""

import cells


def lane_items(ctx, chip: int) -> float:
    lane = {w.device.id: w.index for w in ctx.cr.cores.workers}.get(chip, chip)
    log = [r for r in ctx.ranges_log if len(r) > lane]
    return sum(r[lane] for r in log) / len(log) if log else 0.0


def read(ctx):
    chip, seconds, steps = cells.load_reader(
        "wave_kernel_ms_per_iter").kernel_seconds(ctx)
    items = lane_items(ctx, chip)
    if not steps or not seconds or not items:
        return None
    cost = ctx.cell.ref.kernel_cost(ctx.cfg, ctx.params, items)
    least = max(cost["ops"] / ctx.peaks["flops_per_s"],
                cost["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * steps / seconds
