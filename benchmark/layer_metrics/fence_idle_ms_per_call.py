"""Worst-chip idle per call under ``ck/fence``: a chip that has finished waits
for the barrier's bookkeeping and for the other lanes."""

import host_phases


def read(ctx):
    p = host_phases.of(ctx)
    return None if p is None else p.idle_ms_per_call("fence")
