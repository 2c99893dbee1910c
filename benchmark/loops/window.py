"""Loop kind ``window``: one call is one enqueue window — ``iterations_per_call``
computes of the same kernel enqueued back to back, closed by a barrier — the
way upstream's demo loops drive repeated same-shape work.  Results stay on
the device between calls; ``leave`` flushes them to the host arrays."""


def items_per_call(params: dict) -> int:
    return int(params["n"]) * int(params["iterations_per_call"])


def enter(ctx) -> None:
    ctx.cr.enqueue_mode = True


def make_call(ctx):
    cr, compute, span = ctx.cr, ctx.compute, ctx.span
    iters = int(ctx.params["iterations_per_call"])

    def call() -> None:
        with span("bench/enqueue"):
            for _ in range(iters):
                compute()
        with span("bench/barrier"):
            cr.barrier()

    return call


def leave(ctx) -> None:
    ctx.cr.enqueue_mode = False  # leaving enqueue mode flushes to the host
