"""Device time of one compute of ``reduce`` (the walk into the ``__local``
tile, the tree, the partials' store), from the trace: the time of ALL the
chip's operations inside the window but the containers (``while``,
``conditional``, ``call``: their bodies are counted), copies included, over
the computes the window's calls ran.  Nothing else runs on this chip in the
window: the read-back of the partials is no operation of the device's ``XLA
Ops`` line."""

import cells


def kernel_seconds(ctx) -> tuple[float, int]:
    """(seconds of the chip's operations, computes) in the window: counted
    as ``mvt_kernel_ms_per_iter`` counts a compute of its two kernels."""
    return cells.load_reader("mvt_kernel_ms_per_iter").kernel_seconds(ctx)


def read(ctx):
    seconds, computes = kernel_seconds(ctx)
    return 1e3 * seconds / computes if computes and seconds else None
