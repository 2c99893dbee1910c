"""Loop kind ``per_call``: one call is one non-windowed ``compute()`` — arrays
go to the device, the kernel runs, results come back to the host arrays, and
the caller has them when the call returns."""


def items_per_call(params: dict) -> int:
    return int(params["n"])


def enter(ctx) -> None:
    ctx.cr.enqueue_mode = False


def make_call(ctx):
    return ctx.compute


def leave(ctx) -> None:
    """Nothing is deferred: every call already ended with its results on
    the host."""
