"""Of a call's read-back (``readback_ms_per_call``), the stretches in which
the caller waited for the bytes: ``part:issued`` to ``part:landed`` (with
several downloads a call, from the end of the span before to each
``part:landed``): the kernel's end and the copy over the link into jax's own
host memory.  Host clock alone."""

import cells


def read(ctx):
    r = cells.load_reader("readback_ms_per_call").of(ctx)
    return None if r is None else r.ms_per_call(r.landing_s)
