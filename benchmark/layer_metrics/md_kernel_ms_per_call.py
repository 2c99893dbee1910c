"""Device time of one force step (``compute_lj_force``: the walk through the
neighbour list, a ``float4`` gather a neighbour, the Lennard-Jones sum, the
forces' store), from the trace: the time of ALL the chip's operations inside
the window but the containers (``while``, ``conditional``, ``call``: their
bodies are counted), copies INCLUDED (the row view of the positions is made
anew with every upload of them), over the computes the window's calls ran.
Nothing else runs on this chip in the window: the upload of the positions and
the read-back of the forces are no operations of the device's ``XLA Ops``
line."""

import cells


def kernel_seconds(ctx) -> tuple[float, int]:
    """(seconds of the chip's operations, computes) in the window: counted
    as ``mvt_kernel_ms_per_iter`` counts a compute of its two kernels."""
    return cells.load_reader("mvt_kernel_ms_per_iter").kernel_seconds(ctx)


def read(ctx):
    seconds, computes = kernel_seconds(ctx)
    return 1e3 * seconds / computes if computes and seconds else None
