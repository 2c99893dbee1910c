"""The host's edge of a synchronous ``compute()``, per call, on the HOST's
clock alone: everything of a call's computes outside ``run``, from each
compute's ``ck/enqueue`` open to its first launch's ``part:handed`` (the
caller's way in, the pool hop, the lane's lock, ``classify`` and the uploads,
the dispatch as the runtime admits it) and from its last ``part:landed`` to the
next compute's open or the ``bench/call``'s end (the copy into the caller's
array, the phase's wrap-up, the caller's wake-up and return): the stretches in
which the chip has nothing of this caller's and only the host can hand it
something.  The median over the window's calls of the call's own sum
(``call_edge``); no device line is read, so no shift or scale of the trace
moves it.  A program without the marks of ISSUE 52 (a parent commit) leaves
the metric out."""

import call_edge


def read(ctx):
    e = call_edge.of(ctx)
    return None if e is None else e.edge_ms
