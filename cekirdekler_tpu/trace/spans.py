"""Typed span recording: the host-side half of the attribution subsystem.

The reference's only observability is host-side stopwatches scattered
through the runtime (SURVEY §5.1; Worker.cs:753-807, Cores.cs:994-1063)
and its planned timeline-overlap query is a ``NotImplementedException``
(ClPipeline.cs:2391-2399).  This module replaces ad-hoc stopwatches with
ONE process-global :class:`Tracer`: every runtime layer (worker phases,
both cores pipeline engines, device pipelines, pools, the DCN tier)
records typed :class:`Span` records into a fixed-capacity ring buffer,
tagged with compute id and lane, so a lost millisecond anywhere in the
stack has a name.

Design constraints, in order:

1. **Inactive is free.**  The tracer ships enabled on no hot path by
   default; instrumentation sites pay one ``is_enabled()`` of the
   profiler, one attribute read and a falsy check (<1 µs per would-be
   span, measured by
   ``tests/test_trace.py::test_disabled_tracer_overhead``).  The
   convention at hot sites is the ``t0()``/``record()`` pair::

       t0 = TRACER.t0("launch")  # 0.0 when inactive — no clock read
       ...work...
       TRACER.record("launch", t0, cid=cid, lane=self.index)

   ``t0`` is an opaque token: falsy when the tracer is inactive, and
   whatever :meth:`Tracer.record` needs otherwise.

2. **Lock-free-ish.**  Recording is one ``itertools.count`` increment
   (atomic under the GIL) plus one list-slot store — concurrent worker
   threads never contend on a lock to record.  The ring overwrites the
   oldest spans when full; ``total_recorded`` keeps the true count so a
   wrapped buffer is detectable, never silent.

3. **Two sinks, each on its own clock.**  The tracer is ACTIVE when
   ``TRACER.enabled`` (the ring: ``time.perf_counter()`` seconds,
   comparable across threads within the process) **or** while a
   ``jax.profiler`` session runs.  In a session every site opens a
   ``jax.profiler.TraceAnnotation`` named ``ck/<kind>`` at its start and
   closes it at its end on the same thread, so the program's spans land
   in ``/host:CPU`` of the same ``.xplane.pb`` as the device's ``XLA
   Ops`` lines, on the profiler's clock: an idle gap of a chip can be
   put down to the span that covers it (``benchmark/host_phases.py``).
   Nothing has to switch this on — ``jax.profiler.start_trace`` is the
   switch, and the test for it is ``TraceAnnotation.is_enabled()``
   (bound lazily: this module imports no jax).  Each annotation carries
   as METADATA (xplane stats; ``args`` in the trace-viewer JSON): ``cid``
   and ``lane`` where the site knows them, ``tag``, ``win`` (the
   sequence number of the enqueue window or non-windowed call the span
   belongs to, shared by the caller's thread and the threads that work
   for it), on a driver or pool thread the lane it works for (where the
   site names none) and ``queued_us`` (how long the closure waited
   between its submission and its start), and whatever else the site
   passes by keyword (``bytes``, ``seq``, ``kernel``).

Span kinds used by the built-in instrumentation (callers may add more):
``enqueue`` (a compute() dispatch), ``split`` (first range table),
``rebalance`` (the balancer moved shares), ``launch`` (kernel dispatch),
``fence`` (retirement wait), ``upload`` (H2D), ``download`` (D2H),
``upload-chunk`` / ``download-chunk`` (one ladder-aligned chunk of a
STREAMED partition transfer — the chunked double-buffered H2D/D2H path,
``Cores._run_streamed``; the monolithic kinds above stay for whole-range
transfers so the two paths are distinguishable in every report),
``pipeline-stage`` (one pipeline engine/stage body), ``pool-task``
(device-pool task), ``dcn-exchange`` (cross-host collective), ``fused``
(fused-iteration window flush — spans tag ``xK`` for a K-iteration
ladder dispatch; zero-duration instants tag ``disengage:<reason>`` when
the fused path falls back to per-iteration dispatch, so a silent perf
regression to the slow path is attributable), ``driver-error`` (a
dispatch-driver closure failed — the instant is recorded at failure
time, before the error surfaces at the caller's sync point, so a
postmortem's span ring names the failing dispatch).  The stretches of
``compute()`` / ``barrier()`` that had no span before ISSUE 24:
``schedule`` (the range table and the balancer behind it), ``resync``
(the flush of deferred results and the coverage reset after a range
move, and ``flush()`` itself), ``engage`` (opening a fused window),
``drain`` (the caller waiting for the per-lane driver queues), ``tune``
(the transfer autotuner's ``choose`` / ``observe``; an instant
``chunks:<old>-><new>`` when a lane's chunk count changes) and
``compile`` (the first trace-and-compile of a launcher for a new shape).
"""

from __future__ import annotations

import itertools
import sys
import threading
import time
from contextlib import contextmanager
from typing import Iterable, NamedTuple

__all__ = ["Span", "Tracer", "TRACER", "SPAN_KINDS", "tracing"]

SPAN_KINDS = (
    "enqueue", "split", "rebalance", "launch", "fence",
    "upload", "download", "upload-chunk", "download-chunk",
    "pipeline-stage", "pool-task", "dcn-exchange",
    "fused", "driver-error",
    "schedule", "resync", "engage", "drain", "tune", "compile", "halo",
)

#: annotation names, built once: a span site must not concatenate per span
_NAMES = {k: "ck/" + k for k in SPAN_KINDS}


class _Context(threading.local):
    """What a span inherits from the thread it closes on: the window it
    belongs to, and on a driver or pool thread the lane the closure works
    for and how long it was queued (:meth:`Tracer.bind`)."""

    win: int | None = None
    lane: int | None = None
    queued_us: float | None = None


_CTX = _Context()


class Span(NamedTuple):
    """One timed event.  ``t0``/``t1`` are perf_counter seconds; ``cid``
    is the compute id (None where no compute id applies), ``lane`` the
    worker/consumer index, ``tag`` a short free-form annotation."""

    kind: str
    t0: float
    t1: float
    cid: int | None = None
    lane: int | None = None
    tag: str | None = None

    @property
    def dur_ms(self) -> float:
        return (self.t1 - self.t0) * 1000.0


class Tracer:
    """Process-global span recorder (one instance: :data:`TRACER`).

    ``enabled`` (the ring) is a plain attribute on purpose: the disabled
    fast path must be an attribute read, not a property call.  The other
    sink, a running ``jax.profiler`` session, is asked for by
    :meth:`_session_on`."""

    def __init__(self, capacity: int = 65536):
        self.enabled = False
        self._cap = max(16, int(capacity))
        self._buf: list[Span | None] = [None] * self._cap
        self._count = itertools.count()
        self._total = 0
        self._lock = threading.Lock()  # enable/clear only — never record()
        # ring-wrap losses already exported to the metrics registry
        # (ck_trace_dropped_spans_total) — the delta tracking that keeps
        # the counter monotonic across snapshots within one ring epoch
        self._dropped_reported = 0
        # the profiler bridge: ``_session_on`` is this resolver until jax
        # is there to ask, then ``TraceAnnotation.is_enabled`` itself
        self._ann = None
        self._session_on = self._bind_session
        self._wins = itertools.count(1)

    def _bind_session(self) -> bool:
        """First asks only: without jax imported no session can run, and
        this module must not be what imports it."""
        if "jax" not in sys.modules:
            return False
        try:
            from jax.profiler import TraceAnnotation

            probe = TraceAnnotation.is_enabled
            on = bool(probe())
        except Exception:  # noqa: BLE001 - no profiler: the ring still works
            self._session_on = lambda: False
            return False
        self._ann = TraceAnnotation
        self._session_on = probe
        return on

    # -- recording (hot path) ------------------------------------------------
    def active(self) -> bool:
        """True while either sink takes spans: the guard for work a site
        does only to describe a span (building a tag)."""
        return self.enabled or self._session_on()

    def t0(self, kind: str | None = None):
        """Span-open token: 0.0 when inactive (no clock read), a
        ``perf_counter`` float when only the ring is on, and with a
        profiler session running the opened ``ck/<kind>`` annotation
        beside it.  Pass it to :meth:`record` on the same thread."""
        if kind is not None and self._session_on():
            ann = self._ann(_NAMES.get(kind) or "ck/" + kind)
            ann.__enter__()
            return (ann, time.perf_counter() if self.enabled else 0.0)
        return time.perf_counter() if self.enabled else 0.0

    @staticmethod
    def _close(token, cid, lane, tag, meta: dict) -> float:
        """Close the annotation half of a token with its metadata;
        returns the ring half (0.0 when the ring was off at open)."""
        ann, t0 = token
        ctx = _CTX.__dict__  # ONE thread-local resolution, then dict reads
        if cid is not None:
            meta["cid"] = cid
        if lane is None:
            lane = ctx.get("lane")  # a site that knows no lane (compile)
        if lane is not None:        # takes the one its thread works for
            meta["lane"] = lane
        if tag is not None:
            meta["tag"] = tag
        for k, v in meta.items():
            # '#' ends the metadata block of a TraceMe name and ',' a
            # value in it: a tag ``[2048, 2048]`` would arrive as ``[2048``
            if v.__class__ is str and ("," in v or "#" in v):
                meta[k] = v.replace("#", "").replace(",", ";")
        if ctx:
            win, queued = ctx.get("win"), ctx.get("queued_us")
            if win is not None:
                meta["win"] = win
            if queued is not None:
                meta["queued_us"] = queued
        try:
            if meta:
                ann.set_metadata(**meta)
            ann.__exit__(None, None, None)
        except Exception:  # noqa: BLE001
            pass
        return t0

    def record(
        self,
        kind: str,
        t0,
        cid: int | None = None,
        lane: int | None = None,
        tag: str | None = None,
        t1: float | None = None,
        **meta,
    ) -> None:
        """Close and store a span opened at ``t0``.  No-op when inactive
        or when ``t0`` is the inactive sentinel (0.0) — a site that
        opened its span while the tracer was off records nothing even if
        the tracer was enabled mid-span.  ``meta`` goes to the profiler
        annotation only (the ring's :class:`Span` has no room for it)."""
        if not t0:
            return
        if t0.__class__ is tuple:
            t0 = self._close(t0, cid, lane, tag, meta)
        if not self.enabled or not t0:
            return
        i = next(self._count)  # GIL-atomic slot claim — no lock
        buf = self._buf
        # index by the captured buffer's OWN length, not self._cap: a
        # concurrent enable(capacity=...) swaps buffer and cap in two
        # steps, and mixing one thread's buffer with the other's modulus
        # would IndexError inside instrumented real work
        buf[i % len(buf)] = Span(
            kind, t0, t1 if t1 is not None else time.perf_counter(),
            cid, lane, tag,
        )
        self._total = i + 1  # approximate under races; reporting only

    def instant(
        self,
        kind: str,
        cid: int | None = None,
        lane: int | None = None,
        tag: str | None = None,
    ) -> None:
        """Zero-duration marker (e.g. a rebalance decision): a
        zero-length annotation in a profiler session."""
        tok = self.t0(kind)
        if tok.__class__ is tuple:  # an open annotation: close it at once
            tok = self._close(tok, cid, lane, tag, {})
        if tok:  # the ring's half: one instant, both ends at the open
            self.record(kind, tok, cid=cid, lane=lane, tag=tag, t1=tok)

    @contextmanager
    def span(
        self,
        kind: str,
        cid: int | None = None,
        lane: int | None = None,
        tag: str | None = None,
        **meta,
    ):
        """Context-manager convenience for non-hot sites; records even
        when the body raises (the failing span is usually the one you
        want to see)."""
        t0 = self.t0(kind)
        try:
            yield
        finally:
            self.record(kind, t0, cid=cid, lane=lane, tag=tag, **meta)

    # -- the window a span belongs to, across threads ------------------------
    def next_window(self) -> int:
        """Open the next enqueue window / non-windowed call on the calling
        thread: every span closed on it from now on carries this ``win``,
        and so do the closures it hands to other threads (:meth:`bind`)."""
        _CTX.win = win = next(self._wins)
        return win

    def bind(self, fn, lane: int | None = None):
        """``fn`` itself when inactive.  Active: a wrapper that runs
        ``fn`` on whatever thread picks it up with the submitting
        thread's ``win``, the ``lane`` it works for, and ``queued_us``,
        the time between this call and its start — the queue wait no
        annotation pair can span across threads."""
        if not self.active():
            return fn
        win, t_sub = _CTX.win, time.perf_counter()

        def bound(*args, **kwargs):
            prev = (_CTX.win, _CTX.lane, _CTX.queued_us)
            _CTX.win, _CTX.lane = win, lane
            _CTX.queued_us = round((time.perf_counter() - t_sub) * 1e6, 1)
            try:
                return fn(*args, **kwargs)
            finally:
                _CTX.win, _CTX.lane, _CTX.queued_us = prev

        return bound

    # -- control -------------------------------------------------------------
    def enable(self, capacity: int | None = None, clear: bool = True) -> None:
        pending_drops = 0
        with self._lock:
            # export wrap losses BEFORE any reset below zeroes the
            # baseline: "raise Tracer capacity" (the report's own
            # advice) must not silently eat the losses that motivated it
            pending_drops = self._drop_delta_locked()
            if capacity is not None and capacity != self._cap:
                # resizing rebuilds the ring; with clear=False the newest
                # existing spans migrate so keep=True keeps its promise,
                # and the counters restart so total_recorded/ring-wrap
                # reporting describes the NEW buffer, not the old one
                keep = [] if clear else self._snapshot_locked_free()
                self._cap = max(16, int(capacity))
                self._clear_locked()
                for s in keep[-self._cap:]:
                    i = next(self._count)
                    self._buf[i % self._cap] = s
                    self._total = i + 1
            elif clear:
                self._clear_locked()
            self.enabled = True
        self._inc_dropped(pending_drops)

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            pending_drops = self._drop_delta_locked()
            self._clear_locked()
        self._inc_dropped(pending_drops)

    def _clear_locked(self) -> None:
        self._buf = [None] * self._cap
        self._count = itertools.count()
        self._total = 0
        self._dropped_reported = 0

    # -- inspection ----------------------------------------------------------
    @property
    def total_recorded(self) -> int:
        """Spans recorded since the last clear — exceeds ``capacity``
        when the ring wrapped (older spans were overwritten)."""
        return self._total

    @property
    def dropped_spans(self) -> int:
        """Spans LOST to ring wrap since the last clear (oldest-first
        overwrites) — the count every coverage report must carry:
        attribution totals silently undercount by exactly these spans."""
        return max(0, self._total - self._cap)

    def _sync_dropped_metric(self) -> None:
        """Export ring-wrap losses to ``ck_trace_dropped_spans_total``.
        Called from snapshot() (a cold path) rather than record(): the
        recording path's lock-free contract must not pay a registry
        lock per span once the ring wraps.  Delta-based so the counter
        stays monotonic across repeated snapshots; a clear() resets the
        baseline with the ring.  The delta read-modify-write runs under
        the tracer lock — two concurrent snapshots (the debug server's
        /tracez thread + an in-process report) would otherwise both see
        the same baseline and double-count the loss."""
        with self._lock:
            delta = self._drop_delta_locked()
        self._inc_dropped(delta)

    def _drop_delta_locked(self) -> int:
        """Unreported ring-wrap loss; advances the baseline.  Caller
        holds the tracer lock."""
        d = self.dropped_spans
        delta = d - self._dropped_reported
        if delta <= 0:
            return 0
        self._dropped_reported = d
        return delta

    @staticmethod
    def _inc_dropped(delta: int) -> None:
        if delta <= 0:
            return
        from ..metrics.registry import REGISTRY

        REGISTRY.counter(
            "ck_trace_dropped_spans_total",
            "spans lost to tracer ring wrap (attribution undercounts)",
        ).inc(delta)

    @property
    def capacity(self) -> int:
        return self._cap

    def _snapshot_locked_free(self) -> list[Span]:
        """The span copy alone — no metric sync, no lock.  enable()'s
        keep-path calls this while HOLDING the tracer lock (snapshot()
        there would deadlock on the non-reentrant lock via
        _sync_dropped_metric)."""
        buf = list(self._buf)  # one slice: consistent-enough view
        spans = [s for s in buf if s is not None]
        spans.sort(key=lambda s: s.t0)
        return spans

    def snapshot(self) -> list[Span]:
        """Recorded spans, oldest first.  Concurrent recording during
        the snapshot may drop/duplicate a span at the wrap edge — the
        snapshot is for reporting, not for synchronization."""
        self._sync_dropped_metric()
        return self._snapshot_locked_free()

    def spans_between(self, t_lo: float, t_hi: float) -> list[Span]:
        """Spans that overlap the window [t_lo, t_hi]."""
        return [s for s in self.snapshot() if s.t1 >= t_lo and s.t0 <= t_hi]


#: The process-global tracer every built-in instrumentation site uses.
TRACER = Tracer()


@contextmanager
def tracing(capacity: int | None = None, keep: bool = False,
            metrics: bool = False):
    """Scoped enable of the global tracer::

        with trace.tracing() as tr:
            ...instrumented work...
        report = attribution.window_report(tr.snapshot(), t0, t1)

    Disables on exit; spans survive (``keep`` preserves pre-existing
    spans instead of clearing on entry).  ``metrics=True`` additionally
    turns on registry sampling for the window
    (``metrics.REGISTRY.enable_sampling``), so the counter time series
    for Perfetto counter tracks cover exactly the traced window::

        with trace.tracing(metrics=True) as tr:
            ...work...
        trace.save_chrome_trace(
            tr.snapshot(), path,
            counters=metrics.REGISTRY.counter_series())
    """
    TRACER.enable(capacity=capacity, clear=not keep)
    if metrics:
        from ..metrics.registry import REGISTRY as _REG

        _REG.enable_sampling()
    try:
        yield TRACER
    finally:
        TRACER.disable()
        if metrics:
            from ..metrics.registry import REGISTRY as _REG

            _REG.disable_sampling()


def spans_by_kind(spans: Iterable[Span]) -> dict[str, list[Span]]:
    out: dict[str, list[Span]] = {}
    for s in spans:
        out.setdefault(s.kind, []).append(s)
    return out
