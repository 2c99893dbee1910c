"""Share of the traced window in which no operation ran on the chip, in
percent; with several chips, the worst one."""


def read(ctx):
    r = ctx.reduced
    return 100.0 * r.idle_share(r.worst_chip)
