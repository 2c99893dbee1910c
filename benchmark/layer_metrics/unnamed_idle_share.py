"""Share of the worst chip's idle inside the window under NO ``ck/`` span of
the program, in percent: what the program's tracing still cannot name."""

import host_phases


def read(ctx):
    p = host_phases.of(ctx)
    if p is None or not p.idle_s:
        return None
    return 100.0 * p.by_kind[host_phases.UNNAMED] / p.idle_s
