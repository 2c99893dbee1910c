"""Loop kind ``reduction``: one call is what a host loop that needs a sum of
an array resident on the chip does at every step, through upstream's public
API alone: ONE synchronous non-enqueue ``compute()`` of the reduction kernel
with that call's ``n`` (the partials, one a work-group, are back in the
caller's array when it returns), then the host's float64 sum of the partials,
as SHOC's ``Reduction.cpp`` adds its block sums.  The sum is what the caller
gets; the loop logs it with the call's ``n`` in ``ctx.data["sums"]``, where the
comparison holds every call's sum to the reference.

With ``iterations_per_call`` above 1 a call is that many computes, one after
the other through the cycle of the calls' arguments from its start (the
configuration's rule for a median that spreads too widely over seeds); the
harness's own cycle then only says which call is set apart.

Where the configuration gives an array ``after_first_upload`` (flags by their
public names), ``enter`` sets them: the harness has made its one synchronous
compute by then, so the array is on the chip, and from here on the caller tells
the runtime what the configuration's deployment states, by upstream's own idiom
for data that lives on the device (``read = false``: the lane keeps the buffer
it holds and nothing crosses).  An array without the entry is left alone."""

import numpy as np


def items_per_call(params: dict) -> int:
    return int(params["n"]) * int(params["iterations_per_call"])


def enter(ctx) -> None:
    ctx.cr.enqueue_mode = False
    for spec in ctx.cfg["arrays"]:
        for flag, value in spec.get("after_first_upload", {}).items():
            setattr(ctx.arrays[spec["name"]], flag, value)


def make_call(ctx):
    per_call = int(ctx.params["iterations_per_call"])
    partials = ctx.arrays["g_odata"].host()
    log = ctx.data["sums"]

    def one() -> None:
        ctx.compute()
        log.append((int(ctx.values[0]), float(np.sum(partials, dtype=np.float64))))

    def several() -> None:
        if ctx.values is ctx.apart:
            one()
            return
        for k in range(per_call):
            ctx.values = ctx.cycle[k % len(ctx.cycle)]
            one()

    return one if per_call == 1 else several


def leave(ctx) -> None:
    """Nothing is deferred: every call ended with its partials on the host."""
