"""Share of the levels' device time spent in per-lane gathers, in percent:
the chip's ``gather`` operations and ``kind=kCustom`` fusions that name no
scatter (``bfs_scatter_share.is_gather``), over the levels' time
(``bfs_kernel_ms_per_level``'s): the refill of the adjacency run's window
(row gathers of ``edges``), ``visited[id]`` a pass (a byte gather)."""

import cells


def read(ctx):
    scatter = cells.load_reader("bfs_scatter_share")
    return scatter.share(ctx, scatter.is_gather)
