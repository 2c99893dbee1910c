"""Time the closures of the worst chip's lane waited between their submission
(``dispatch_async``, the worker pool) and their start, per call: ``queued_us``
of the program's spans, each closure counted once."""

import host_phases


def read(ctx):
    p = host_phases.of(ctx)
    return None if p is None else 1e3 * p.queue_wait_s / p.calls
