"""Bytes a traversal sent to the chip: the ``bytes`` of the lane's
``ck/upload`` spans inside a ``bench/call`` span, per call
(``levels_per_call.reduce``).  Rodinia's BFS at 1 000 192 work-items: the four
state arrays at the call's start (3 x 1 000 192 ``char`` + 4 000 768 of
``cost`` = 7 001 344) and the one-byte flag a level, 7 001 356 for 12 levels.
More says an array with ``read = false`` crossed the link again (the 24 MB edge
table after a flag flip)."""

import cells


def read(ctx):
    r = cells.load_reader("levels_per_call").of(ctx)
    return None if r is None else r.upload_bytes / r.calls
