"""Exchanges inside the traced window that went through host memory: the
``ck/halo`` spans whose tag names ``host`` (lanes on different platforms: the
strip is read back and uploaded) and not ``d2d`` alone.  0 where every strip
went device to device.  A program without the span leaves nothing to read."""

import cells


def host_hops(spans):
    if not spans:
        return None
    return float(sum("host" in str(s.stats.get("tag", "")) for s in spans))


def read(ctx):
    return host_hops(
        cells.load_reader("halo_idle_ms_per_call").halo_spans(ctx))
