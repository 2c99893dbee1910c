"""The traversals' share of their roofline: the least time the chip could
take for the levels the window's calls ran, the bytes the ALGORITHM has to
move (the configuration's ``kernel_cost``, level by level from the plain
reference's frontier, edge entries and discovered nodes of each call's own
source) over the peak HBM bytes/s, over the device time of those levels
(``bfs_kernel_ms_per_level``'s).  Bounded by memory: compares and one add.
Small by the algorithm's nature: a level touches every node's two mask bytes
and a frontier of thousands moves a few kilobytes, while the lowering runs
every pass of the adjacency loop over the whole range."""

import cells


def least_bytes(ctx, calls: int) -> float:
    """The window's ``calls`` are the log's entries before the fresh call's."""
    ref, data = ctx.cell.ref, ctx.data
    log = data["traversals"][-1 - calls:-1]
    per_source: dict = {}
    total = 0.0
    for source, _levels in log:
        if source not in per_source:
            stats: list = []
            ref.bfs(data["starting"], data["no_of_edges"], data["edges"],
                    int(data["relabel"][source]), stats)
            per_source[source] = sum(
                ref.kernel_cost(ctx.cfg, ctx.params, ctx.n, *level)["bytes"]
                for level in stats)
        total += per_source[source]
    return total


def read(ctx):
    seconds, levels = cells.load_reader(
        "bfs_kernel_ms_per_level").kernel_seconds(ctx)
    if not levels or not seconds:
        return None
    return (100.0 * least_bytes(ctx, ctx.reduced.calls)
            / ctx.peaks["hbm_bytes_per_s"] / seconds)
