"""The benchmark's own reduction from a profiler trace (``.xplane.pb``) to
device busy / idle time, per-operation time and idle gaps named by what the
host was doing.  Kept with the benchmark so that every PR computes these
numbers the same way; ``checks/test_trace_reduction.py`` holds it to a
synthetic trace with hand-computed answers and to a recorded one.

What a TPU trace looks like (read off a v5e trace, PR 23): one plane per chip
named ``/device:TPU:<i>`` whose line ``XLA Ops`` holds one event per executed
HLO operation (the event's name is the operation's HLO text) and whose line
``XLA Modules`` holds one event per executable; one plane ``/host:CPU`` with a
line per host thread, on which ``jax.profiler.TraceAnnotation`` spans appear by
name.  Device and host events share one clock (nanoseconds from the start of
the profile).  All times below are seconds on that clock.
"""

from __future__ import annotations

import bisect
import gzip
import os
import re
from typing import Iterable, NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
OUTSIDE = "outside-bench-spans"
#: operations that only contain others (a fused window is one ``while`` over
#: a ``conditional`` ladder): they count towards the busy union, and are left
#: out of the list of operations that took most time, which their bodies explain
CONTAINERS = ("while", "conditional", "call")

# "%copy.3 = f32[8]{0:T(1024)} copy(f32[8]{0} %buf.1)" -> ("copy.3", "copy"):
# the opcode is the first lower-case word directly followed by "(" and
# preceded by a blank (layouts such as T(8,128) follow ":" or ")", tuples
# follow "= " with no word before the bracket)
_HLO = re.compile(r"^%?(?P<name>[^\s=]+)\s*=\s.*?\s(?P<opcode>[a-z][a-z0-9\-]*)\(")


class Op(NamedTuple):
    name: str      # "copy.3"
    opcode: str    # "copy"; "" where the event is not HLO text
    start: float
    end: float


class Span(NamedTuple):
    name: str
    start: float
    end: float


def op_label(text: str) -> tuple[str, str]:
    m = _HLO.match(text)
    if m is None:
        return text.lstrip("%"), ""
    return m.group("name"), m.group("opcode")


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = []
    for root, _dirs, files in os.walk(trace_dir):
        found += [os.path.join(root, f) for f in files
                  if f.endswith(".xplane.pb")]
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def _profile(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".txt"):  # a text-format XSpace (the synthetic fixture)
        with open(path, encoding="utf-8") as f:
            return ProfileData.from_text_proto(f.read())
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


class Trace(NamedTuple):
    devices: dict   # chip index -> [Op] sorted by start
    spans: list     # [Span] of the host annotations asked for, by start


def load(path: str, span_prefix: str = "bench/") -> Trace:
    devices: dict[int, list[Op]] = {}
    spans: list[Span] = []
    for plane in _profile(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m is not None:
            ops = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name, opcode = op_label(ev.name)
                    t0 = ev.start_ns * 1e-9
                    ops.append(Op(name, opcode, t0,
                                  t0 + ev.duration_ns * 1e-9))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        t0 = ev.start_ns * 1e-9
                        spans.append(Span(ev.name, t0,
                                          t0 + ev.duration_ns * 1e-9))
    for ops in devices.values():
        ops.sort(key=lambda o: o.start)
    spans.sort(key=lambda s: s.start)
    return Trace(devices, spans)


def merged(intervals: Iterable[tuple[float, float]], t0: float,
           t1: float) -> list[tuple[float, float]]:
    """Union of the intervals, clipped to [t0, t1], as disjoint sorted
    intervals."""
    out: list[list[float]] = []
    for a, b in sorted((max(a, t0), min(b, t1)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps_of(busy: list[tuple[float, float]], t0: float,
            t1: float) -> list[tuple[float, float]]:
    out, at = [], t0
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if t1 > at:
        out.append((at, t1))
    return out


def innermost_segments(spans: list[Span]) -> list[tuple[float, float, str]]:
    """Cut the time covered by spans into segments, each named by the
    shortest span that covers it (the innermost one)."""
    cuts = sorted({t for s in spans for t in (s.start, s.end)})
    segs = []
    by_len = sorted(spans, key=lambda s: s.end - s.start)
    for a, b in zip(cuts, cuts[1:]):
        mid = 0.5 * (a + b)
        for s in by_len:
            if s.start <= mid < s.end:
                segs.append((a, b, s.name))
                break
    return segs


def attribute(gaps: list[tuple[float, float]],
              spans: list[Span]) -> dict[str, float]:
    """Idle seconds by the innermost host span covering them."""
    segs = innermost_segments(spans)
    starts = [s[0] for s in segs]
    out: dict[str, float] = {}
    for a, b in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(segs) and segs[i][0] < b:
            lo, hi = max(a, segs[i][0]), min(b, segs[i][1])
            if hi - lo > 1e-12:
                out[segs[i][2]] = out.get(segs[i][2], 0.0) + (hi - lo)
                covered += hi - lo
            i += 1
        if (b - a) - covered > 1e-12:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (b - a) - covered
    return out


class Reduced(NamedTuple):
    t0: float
    t1: float
    busy_s: dict        # chip -> seconds in which an operation ran
    op_seconds: dict    # chip -> {(name, opcode): seconds}, clipped
    op_counts: dict     # chip -> {(name, opcode): events starting inside}
    idle_by_span: dict  # chip -> {span name: idle seconds}
    calls: int          # top-level bench spans inside the window

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def idle_share(self, chip: int) -> float:
        return 1.0 - self.busy_s[chip] / self.window_s

    @property
    def worst_chip(self) -> int:
        return max(self.busy_s, key=self.idle_share)


def reduce(trace: Trace, top_span: str = "bench/call") -> Reduced:
    """The window is the stretch from the first ``top_span`` to the end of
    the last: what the benchmark drove, without the profiler's own start and
    stop."""
    tops = [s for s in trace.spans if s.name == top_span]
    if not tops:
        raise ValueError(f"no {top_span!r} span in the trace")
    if not trace.devices:
        raise ValueError("no /device:TPU plane in the trace")
    t0, t1 = tops[0].start, max(s.end for s in tops)
    busy_s, op_seconds, op_counts, idle = {}, {}, {}, {}
    for chip, ops in trace.devices.items():
        busy = merged(((o.start, o.end) for o in ops), t0, t1)
        busy_s[chip] = sum(b - a for a, b in busy)
        secs: dict = {}
        counts: dict = {}
        for o in ops:
            d = min(o.end, t1) - max(o.start, t0)
            if d <= 0:
                continue
            key = (o.name, o.opcode)
            secs[key] = secs.get(key, 0.0) + d
            if t0 <= o.start < t1:
                counts[key] = counts.get(key, 0) + 1
        op_seconds[chip], op_counts[chip] = secs, counts
        idle[chip] = attribute(gaps_of(busy, t0, t1), trace.spans)
    return Reduced(t0, t1, busy_s, op_seconds, op_counts, idle, len(tops))


def seconds_of(reduced: Reduced, chip: int, opcode: str) -> tuple[float, int]:
    """(seconds, events) of the operations with this opcode on one chip."""
    s = sum(v for (_n, oc), v in reduced.op_seconds[chip].items()
            if oc == opcode)
    n = sum(v for (_n, oc), v in reduced.op_counts[chip].items()
            if oc == opcode)
    return s, n


def breakdown(reduced: Reduced, top: int = 10) -> dict:
    """The ten device operations that took most time (summed over chips) and
    the longest idle stretches by host span (worst chip)."""
    total: dict[str, float] = {}
    for secs in reduced.op_seconds.values():
        for (name, opcode), v in secs.items():
            if opcode in CONTAINERS:
                continue
            label = f"{name}/{opcode}" if opcode else name
            total[label] = total.get(label, 0.0) + v
    ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(reduced.idle_by_span[reduced.worst_chip].items(),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
