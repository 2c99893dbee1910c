"""Run-time scalars that crossed from the host to the chip ONE BY ONE, per
call: the ``loose`` of the ``scalars=packed:W;loose:L`` field on the
``ck/launch`` spans of the worst chip's lane
(``launch_ms_per_call.reduce``), summed over a call's launches.  Each is a
host-to-device transfer of one Python or numpy number inside the dispatch; 0
where every dispatch handed its offset and the kernel's value arguments over
as one packed vector.  A program whose spans carry no such field (a parent
commit) leaves nothing to read."""

import cells


def read(ctx):
    r = cells.load_reader("launch_ms_per_call").of(ctx)
    return None if r is None or r.loose is None else float(r.per_call(r.loose))
