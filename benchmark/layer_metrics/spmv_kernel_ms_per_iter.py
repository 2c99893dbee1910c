"""Device time of one sparse matrix-vector product, from the trace: the time
of the chip's operations inside the window, the containers (``while``,
``conditional``, ``call``: their bodies are counted) and the copies left out,
over the products the window's calls ran.  The kernel has no operation of its
own to look for: on the vectorized-XLA lowering it is whatever XLA made of
it (gather fusions, loop fusions, a reduction in the loop's condition), so
all of the chip's work but the copies is the kernel's."""

import xplane

COPIES = ("copy", "copy-start", "copy-done")


def chip_of(reduced) -> int:
    """The chip that worked: the busiest (the cell has one)."""
    return max(reduced.busy_s, key=reduced.busy_s.get)


def kernel_seconds(ctx) -> tuple[float, int]:
    """(seconds of the kernel's operations, products) in the window."""
    r = ctx.reduced
    seconds = sum(v for (_name, opcode), v in r.op_seconds[chip_of(r)].items()
                  if opcode not in xplane.CONTAINERS and opcode not in COPIES)
    return seconds, r.calls * int(ctx.params["iterations_per_call"])


def read(ctx):
    seconds, products = kernel_seconds(ctx)
    return 1e3 * seconds / products if products and seconds else None
