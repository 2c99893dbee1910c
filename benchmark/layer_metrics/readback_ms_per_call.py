"""A call's read-back on the HOST's clock alone, per call: from the
``part:issued`` mark of the call's first download (``Worker.download_async``,
the line after ``copy_to_host_async``) to the end of its last ``ck/download``
/ ``ck/download-chunk`` span (``Worker.finish_download``: the frame is in the
caller's array).  The shared reduction of the five ``readback_*`` readers.

Since ISSUE 38 the program cuts a download by two instants that ride the
span's own kind and carry ``lane``, ``bytes``, ``name`` (the array's) and
``off``: ``part:issued`` where the copy to the host is on its way, and
``part:landed`` inside the span on the line after ``np.asarray`` returned (the
bytes are in jax's own host memory).  A call (one ``win``) may hold several
downloads (the streamed path's chunks): they are issued on the lane's stream
driver thread and finished one after another on the lane's phase thread, so
the whole falls into two kinds of stretch that add up to it exactly:

- **copy**: ``part:landed`` to its span's end, summed: the second pass, from
  jax's host buffer into the caller's array;
- **landing**: the rest, from the first ``part:issued`` (then from the end of
  the span before) to each ``part:landed``: the caller waits for the kernel
  and for the bytes.  With one download a call that is issued -> landed.

A call the transfer tuner FENCED before its read-back (its measuring run: the
lane's ``ck/fence`` ``retired`` instant lies in the call, before the first
``part:issued``) waited for no kernel: its landing is the link's own time for
the call's bytes, and the log line gives it apart (no metric of its own).

The worst chip's lane, as ``host_phases`` picks it; calls whose read-back lies
inside the traced window.  No device line is read.  A program without the
marks (a parent commit) or a cell without downloads leaves nothing to read:
``of`` returns None and every reader leaves its metric out.
``checks/test_display_cells.py`` holds it to a trace made by hand."""

from typing import NamedTuple

import host_phases
import xplane

KINDS = (host_phases.PREFIX + "download", host_phases.PREFIX + "download-chunk")
FENCE = host_phases.PREFIX + "fence"
ISSUED, LANDED = "part:issued", "part:landed"


class Readback(NamedTuple):
    calls: int        # calls (``win``) with a whole read-back in the window
    whole_s: float    # first issued -> last span's end, summed over them
    landing_s: float
    copy_s: float
    bytes: float      # the ``part:landed`` marks' ``bytes``, summed
    downloads: int
    fenced_calls: int = 0        # calls fenced before their read-back, and
    fenced_landing_s: float = 0.0  # their landing: the link alone
    fenced_bytes: float = 0.0

    def ms_per_call(self, seconds: float) -> float:
        return 1e3 * seconds / self.calls


def reduce(lines, t0: float, t1: float, lane: int) -> Readback | None:
    """``lines``: ``host_phases.host_lines``.  Downloads of ``lane`` grouped
    by ``win``; a call counts if it has an issued mark, and every span of it
    holds its landed mark and lies inside [t0, t1]."""
    calls: dict = {}
    retired: dict = {}  # win -> when the lane's fence inside the call retired
    for spans in lines:
        for s in spans:
            if (s.name == FENCE and s.stats.get("lane") == lane
                    and str(s.stats.get("tag")) == "retired"
                    and "win" in s.stats):
                retired[s.stats["win"]] = s.start
        mine = [s for s in spans if s.name in KINDS
                and s.stats.get("lane") == lane and "win" in s.stats]
        marks = [s for s in mine if str(s.stats.get("tag")) == LANDED]
        for s in mine:
            tag, call = str(s.stats.get("tag")), calls.setdefault(
                s.stats["win"], {"issued": [], "downloads": []})
            if tag == ISSUED:
                call["issued"].append(s.start)
            elif tag != LANDED:
                landed = [m for m in marks
                          if s.start <= m.start and m.end <= s.end]
                call["downloads"].append((s, landed[0] if landed else None))
    whole = landing = copy = nbytes = 0.0
    n_calls = n_downloads = n_fenced = 0
    fenced_landing = fenced_bytes = 0.0
    for win, call in calls.items():
        downloads = sorted(call["downloads"], key=lambda d: d[0].start)
        if not call["issued"] or not downloads or any(
                m is None for _s, m in downloads):
            continue
        at = first = min(call["issued"])
        if first < t0 or downloads[-1][0].end > t1:
            continue
        call_landing = call_bytes = 0.0
        for span, mark in downloads:
            call_landing += max(mark.start - at, 0.0)
            copy += span.end - mark.start
            call_bytes += float(mark.stats.get("bytes", 0))
            at = span.end
        landing += call_landing
        nbytes += call_bytes
        whole += at - first
        if win in retired and retired[win] <= first:
            n_fenced += 1
            fenced_landing += call_landing
            fenced_bytes += call_bytes
        n_calls += 1
        n_downloads += len(downloads)
    if not n_calls:
        return None
    return Readback(n_calls, whole, landing, copy, nbytes, n_downloads,
                    n_fenced, fenced_landing, fenced_bytes)


def of(ctx) -> Readback | None:
    """The run's reduction, made once and kept on ``ctx`` for the five
    readers (they run before ``run.py`` removes the trace)."""
    if not hasattr(ctx, "readback"):
        lanes = {w.device.id: w.index for w in ctx.cr.cores.workers}
        chip = ctx.reduced.worst_chip
        ctx.readback = r = reduce(
            host_phases.host_lines(
                xplane._profile(xplane.find_xplane(host_phases.TRACE_DIR))),
            ctx.reduced.t0, ctx.reduced.t1, lanes.get(chip, chip))
        if r is not None:
            print(f"[bench] read-back: {r.calls} calls, {r.downloads} "
                  f"downloads, {r.bytes / r.calls:.0f} bytes a call; ms a "
                  f"call: whole {r.ms_per_call(r.whole_s):.3f} = landing "
                  f"{r.ms_per_call(r.landing_s):.3f} + copy "
                  f"{r.ms_per_call(r.copy_s):.3f}; "
                  f"{r.bytes / r.whole_s / 1e9:.3f} GB/s"
                  + (f"; {r.fenced_calls} calls fenced before their read-back "
                     f"(no kernel to wait for): landing "
                     f"{1e3 * r.fenced_landing_s / r.fenced_calls:.3f} ms, "
                     f"{r.fenced_bytes / r.fenced_landing_s / 1e9:.3f} GB/s "
                     "over the link alone" if r.fenced_landing_s else ""),
                  flush=True)
    return ctx.readback


def read(ctx):
    r = of(ctx)
    return None if r is None else r.ms_per_call(r.whole_s)
