"""How often the lanes' ranges changed from one call to the next inside the
window (``ranges_of`` read after every call).  One lane has nothing to
read."""


def read(ctx):
    log = ctx.ranges_log
    if not log or len(log[0]) < 2:
        return None
    return float(sum(a != b for a, b in zip(log, log[1:])))
