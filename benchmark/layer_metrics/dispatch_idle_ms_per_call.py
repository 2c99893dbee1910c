"""Worst-chip idle per call under the program's dispatch spans: ``ck/enqueue``
(self time), ``ck/engage``, ``ck/fused``, ``ck/drain``, ``ck/launch`` and any
``ck/`` kind no other group names: ``host_phases`` group ``dispatch``."""

import host_phases


def read(ctx):
    p = host_phases.of(ctx)
    return None if p is None else p.idle_ms_per_call(host_phases.DISPATCH)
