"""(max - min) / max of the chips' device-busy time inside the traced window,
in percent: what the balancer leaves on the table.  One chip has nothing to
read."""


def read(ctx):
    busy = list(ctx.reduced.busy_s.values())
    if len(busy) < 2:
        return None
    return 100.0 * (max(busy) - min(busy)) / max(busy)
