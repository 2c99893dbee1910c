"""Compiles (cache reads included) that jax reported inside the measured
window.  Expected 0: a non-zero count explains an outlier."""


def read(ctx):
    return float(ctx.window_compiles)
