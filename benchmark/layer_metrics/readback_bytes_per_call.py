"""Bytes a call read back: the ``bytes`` of its ``part:landed`` marks, per
call.  The output's own size is expected (16 777 216 for a 2048 x 2048
float32 frame): more means a whole buffer came back where a range was due,
fewer a hole."""

import cells


def read(ctx):
    r = cells.load_reader("readback_ms_per_call").of(ctx)
    return None if r is None else r.bytes / r.calls
