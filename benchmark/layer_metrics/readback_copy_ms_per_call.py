"""Of a call's read-back (``readback_ms_per_call``), the second pass:
``part:landed`` to the end of its ``ck/download`` / ``ck/download-chunk`` span,
summed over the call's downloads: the copy from jax's host buffer into the
caller's array (``Worker.finish_download``).  Host clock alone."""

import cells


def read(ctx):
    r = cells.load_reader("readback_ms_per_call").of(ctx)
    return None if r is None else r.ms_per_call(r.copy_s)
