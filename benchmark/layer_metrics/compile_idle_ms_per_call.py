"""Worst-chip idle per call under ``ck/compile`` (a launcher's first
trace-and-compile); expected 0 beside ``window_compiles`` 0."""

import host_phases


def read(ctx):
    p = host_phases.of(ctx)
    return None if p is None else p.idle_ms_per_call("compile")
