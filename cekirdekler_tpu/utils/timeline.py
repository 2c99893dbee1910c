"""Device-timeline capture and analysis over ``jax.profiler``.

The reference's only profiling is host-side stopwatches (SURVEY.md §5.1;
Worker.cs:753-807, Cores.cs:994-1063) and its planned timeline-overlap query
is a ``NotImplementedException`` (ClPipeline.cs:2391-2399).  This module is
the TPU-native upgrade: capture an Xprof trace around any region, then
answer "how busy was the chip, and how much of the wall time did compute
cover?" from the DEVICE-side event stream instead of host stopwatches.

What is reduced here is the device planes' "XLA Ops" tracks: compute
busy time and span — the evidence overlap claims need ("during the
pipelined run the compute stream was busy X% of the makespan; transfers
supplied it without starving it").  Transfer (DMA) tracks are not reduced.
Checked against the installed profiler on a v5e (jax 0.9.0): the dump is
``plugins/profile/<ts>/<host>.trace.json.gz`` beside the ``.xplane.pb``,
device processes are named ``/device:TPU:<n>``, their op track ``XLA Ops``.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from contextlib import contextmanager
from dataclasses import dataclass, field

__all__ = [
    "DeviceTimeline", "Tracer", "capture", "analyze_trace_dir",
    "load_trace_events", "start_profiler", "stop_profiler",
]


@dataclass
class DeviceTimeline:
    """Busy/span statistics of one captured region, from device events."""

    compute_busy_ms: float = 0.0      # union of XLA-op intervals on device
    span_ms: float = 0.0              # first device event start → last end
    n_events: int = 0
    n_devices: int = 0
    per_device_busy_ms: dict = field(default_factory=dict)
    trace_path: str | None = None

    @property
    def compute_busy_fraction(self) -> float:
        """Fraction of the device-event makespan covered by compute — the
        timeline-derived overlap evidence (1.0 = transfers fully hidden
        behind compute; small = the chip sat idle between kernels)."""
        return self.compute_busy_ms / self.span_ms if self.span_ms > 0 else 0.0


def _merged_busy(intervals: list[tuple[float, float]]) -> float:
    """Total length of the union of (start, end) intervals (µs)."""
    if not intervals:
        return 0.0
    intervals.sort()
    total = 0.0
    cur_s, cur_e = intervals[0]
    for s, e in intervals[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return total + (cur_e - cur_s)


def load_trace_events(trace_dir: str) -> tuple[str | None, list]:
    """(path, traceEvents) of the newest trace-event dump under
    ``trace_dir`` — the shared loader behind :func:`analyze_trace_dir`
    and ``trace/device.py``'s richer parse.  Accepts both the gzipped
    form every ``jax.profiler.trace`` on a JSON-emitting backend writes
    (``*.trace.json.gz``) and a plain ``*.trace.json`` (synthetic
    fixtures, hand-converted dumps).  Returns ``(None, [])`` when the
    directory holds no dump or the newest one does not parse — callers
    degrade to an empty analysis, never raise."""
    files = glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True
    ) + glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json"), recursive=True
    )
    if not files:
        return None, []
    path = max(files, key=os.path.getmtime)
    try:
        if path.endswith(".gz"):
            with gzip.open(path, "rt") as f:
                trace = json.load(f)
        else:
            with open(path) as f:
                trace = json.load(f)
    except (OSError, json.JSONDecodeError, UnicodeDecodeError, EOFError):
        return None, []
    # real dumps are ``{"traceEvents": [...]}``; some converters emit
    # the bare event array — accept both (the r8 real-format check)
    if isinstance(trace, list):
        return path, trace
    return path, trace.get("traceEvents", [])


def analyze_trace_dir(trace_dir: str) -> DeviceTimeline:
    """Parse the newest trace dump under ``trace_dir`` and reduce
    the device-side "XLA Ops" tracks to busy/span statistics."""
    path, events = load_trace_events(trace_dir)
    if path is None:
        return DeviceTimeline()
    device_pids: dict[int, str] = {}
    op_tracks: set[tuple[int, int]] = set()
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            name = e.get("args", {}).get("name", "")
            if "/device:" in name:
                device_pids[e["pid"]] = name
        elif e.get("name") == "thread_name":
            if e.get("args", {}).get("name") == "XLA Ops":
                op_tracks.add((e["pid"], e["tid"]))
    per_dev: dict[str, list[tuple[float, float]]] = {}
    lo, hi, count = float("inf"), float("-inf"), 0
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        if (e["pid"], e.get("tid")) not in op_tracks:
            continue
        s = float(e.get("ts", 0.0))
        d = float(e.get("dur", 0.0))
        per_dev.setdefault(device_pids[e["pid"]], []).append((s, s + d))
        lo, hi = min(lo, s), max(hi, s + d)
        count += 1
    busy = {k: _merged_busy(v) / 1000.0 for k, v in per_dev.items()}
    return DeviceTimeline(
        compute_busy_ms=sum(busy.values()),
        span_ms=(hi - lo) / 1000.0 if count else 0.0,
        n_events=count,
        n_devices=len(per_dev),
        per_device_busy_ms=busy,
        trace_path=path,
    )


def start_profiler(trace_dir: str):
    """Start a ``jax.profiler`` trace into ``trace_dir`` — the capture
    seam ``trace/device.py`` builds on.  Returns ``(handle, None)`` on
    success or ``(None, reason)`` when profiling is unavailable (the
    region should still run; degrade to a named absence)."""
    try:
        import jax

        prof = jax.profiler.trace(trace_dir)
        prof.__enter__()
        return prof, None
    except Exception as e:  # noqa: BLE001 - unavailability is a reason
        return None, f"{type(e).__name__}: {e}"


def stop_profiler(handle) -> None:
    """Stop a profiler started by :func:`start_profiler` (best-effort:
    Xprof teardown failures never mask the region's own outcome)."""
    if handle is None:
        return
    try:
        handle.__exit__(None, None, None)
    except Exception:  # noqa: BLE001
        pass


@contextmanager
def capture(trace_dir: str):
    """Capture a device timeline around a region::

        with timeline.capture("/tmp/trace") as result:
            ...work...
        print(result().compute_busy_fraction)

    Yields a zero-arg callable returning the :class:`DeviceTimeline`
    (analyzed lazily, after the region closes).  If the backend cannot
    profile, the region still runs and the analysis is empty.  Exceptions
    raised INSIDE the region propagate unchanged (profiler stopped
    best-effort) — only profiler-start failures are swallowed."""
    state: dict = {}
    prof, _err = start_profiler(trace_dir)
    if prof is None:
        # profiling unavailable: run the region untraced rather than fail
        yield lambda: state.setdefault("tl", DeviceTimeline())
        return
    try:
        yield lambda: state.setdefault("tl", analyze_trace_dir(trace_dir))
    finally:
        stop_profiler(prof)


class Tracer:
    """Reusable tracer: each ``region(name)`` captures into its own subdir
    and records the analyzed :class:`DeviceTimeline` under that name."""

    def __init__(self, base_dir: str):
        self.base_dir = base_dir
        self.regions: dict[str, DeviceTimeline] = {}

    @contextmanager
    def region(self, name: str):
        sub = os.path.join(self.base_dir, name)
        with capture(sub) as result:
            yield
        self.regions[name] = result()

    def report(self) -> str:
        lines = []
        for name, tl in self.regions.items():
            lines.append(
                f"{name}: busy {tl.compute_busy_ms:.3f} ms / span {tl.span_ms:.3f} ms "
                f"({100.0 * tl.compute_busy_fraction:.1f}% busy, {tl.n_events} events)"
            )
        return "\n".join(lines) or "(no regions captured)"
