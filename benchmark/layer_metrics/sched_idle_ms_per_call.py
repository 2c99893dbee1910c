"""Worst-chip idle per call while the program was in ``ck/schedule`` (the range
table and the balancer behind it; the ``split`` / ``rebalance`` instants fall
there): ``host_phases`` group ``sched``."""

import host_phases


def read(ctx):
    p = host_phases.of(ctx)
    return None if p is None else p.idle_ms_per_call("sched")
