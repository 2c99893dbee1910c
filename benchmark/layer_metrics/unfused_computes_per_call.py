"""``ck/enqueue`` spans per call that took the per-call path (tag without
``fused-defer``), counted on the caller's thread inside the window: the
computes of a window that did not ride the fused ladder."""

import host_phases


def read(ctx):
    p = host_phases.of(ctx)
    return None if p is None else p.unfused_computes / p.calls
