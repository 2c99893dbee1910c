"""Device time of one step of the membrane (both kernels, and what the
exchange costs the chip: the strips cut out and laid in), from the trace: the
time of ALL operations of the chip that worked longest inside the window but
the containers (``while``, ``conditional``, ``call``: their bodies are
counted), copies INCLUDED, over the steps the window's calls ran (as
``mvt_kernel_ms_per_iter``)."""

import xplane


def chip_seconds(reduced) -> dict:
    """chip -> seconds of its operations but the containers."""
    return {chip: sum(v for (_name, opcode), v in ops.items()
                      if opcode not in xplane.CONTAINERS)
            for chip, ops in reduced.op_seconds.items()}


def kernel_seconds(ctx) -> tuple[int, float, int]:
    """(the chip, seconds of its operations, steps) in the window."""
    r = ctx.reduced
    seconds = chip_seconds(r)
    chip = max(seconds, key=seconds.get)
    return chip, seconds[chip], r.calls * int(ctx.params["iterations_per_call"])


def read(ctx):
    _chip, seconds, steps = kernel_seconds(ctx)
    return 1e3 * seconds / steps if steps and seconds else None
