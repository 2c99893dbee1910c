"""Worst-chip idle per call while the program made the host current and moved
data: ``ck/resync`` (the flush and coverage reset after a range move),
``ck/upload``, ``ck/download``, the chunk kinds and ``ck/tune``:
``host_phases`` group ``resync``."""

import host_phases


def read(ctx):
    p = host_phases.of(ctx)
    return None if p is None else p.idle_ms_per_call("resync")
