"""Median wall of a call in the streamed cell, where per-call numbers do not
decide a PR."""

import statistics


def read(ctx):
    return 1e3 * statistics.median(ctx.walls)
