"""Device time of copy, slice and update-slice operations per call, from
the trace: what staging the chunks costs the chip itself."""

import xplane

COPY_OPCODES = ("copy", "copy-start", "copy-done", "dynamic-slice",
                "dynamic-update-slice", "slice")


def read(ctx):
    r = ctx.reduced
    chip = r.worst_chip
    total = sum(xplane.seconds_of(r, chip, oc)[0] for oc in COPY_OPCODES)
    return 1e3 * total / r.calls
