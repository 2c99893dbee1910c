"""The bytes a call's downloads carried (the marks' ``bytes``) over the call's
read-back on the host's clock (``readback_ms_per_call``), in GB/s: a rate, not
a share of a peak (the link has no published one: ``peaks.json``)."""

import cells


def read(ctx):
    r = cells.load_reader("readback_ms_per_call").of(ctx)
    return None if r is None or not r.whole_s else r.bytes / r.whole_s / 1e9
