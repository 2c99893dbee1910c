"""Window wall minus the device's busy union, per kernel iteration: what the
host's dispatch costs an iteration once the device could have been running."""


def read(ctx):
    r = ctx.reduced
    iters = r.calls * int(ctx.params["iterations_per_call"])
    chip = r.worst_chip
    return 1e3 * (r.window_s - r.busy_s[chip]) / iters
