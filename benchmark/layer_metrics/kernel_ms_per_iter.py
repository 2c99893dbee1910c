"""Device time of the kernel's own operations per launch, from the trace.
The configuration names the opcode its kernel runs as (a Mosaic kernel is a
``custom-call``); with several chips, the busiest one."""

import xplane


def kernel_time(ctx):
    """(seconds, launches) of the kernel's operations on the chip where
    they took longest."""
    r = ctx.reduced
    return max(xplane.seconds_of(r, chip, ctx.cfg["kernel_opcode"])
               for chip in r.busy_s)


def read(ctx):
    seconds, launches = kernel_time(ctx)
    return 1e3 * seconds / launches if launches else None
