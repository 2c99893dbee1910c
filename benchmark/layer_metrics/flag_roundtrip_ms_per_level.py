"""The stop flag's way up and back on the HOST's clock, per level: the
lengths of the ``ck/upload`` and ``ck/download`` spans that carry the flag
array's name (``over``), summed over a call and divided by its levels
(``levels_per_call.reduce``).  The upload is the host's hand-over of one byte;
the download span runs from where the host asks for the byte to where it is
in the caller's array, and the level's kernels retire inside it (a
synchronous compute waits for them nowhere else), so this is what a level
costs the host beyond its dispatch, kernels included: the log line gives the
two parts."""

import cells


def read(ctx):
    r = cells.load_reader("levels_per_call").of(ctx)
    if r is None or not r.levels or not r.flag_moves:
        return None
    return 1e3 * (r.flag_up_s + r.flag_down_s) / r.levels
