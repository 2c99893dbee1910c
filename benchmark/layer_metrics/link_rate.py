"""Bytes a call must move over the host link — two reads and one write of the
range, four bytes each — over the window's wall, in GB/s."""


def read(ctx):
    moved = 12.0 * ctx.n * len(ctx.walls)
    return moved / ctx.wall / 1e9
