#!/usr/bin/env python
"""Pretty-print a metrics-registry snapshot: live (drive a small
instrumented workload in this process), or polled over HTTP from another
process's debug server (``--url`` + ``--watch``).

The registry is process-local, so "live" means THIS process: with
``--demo`` the tool runs a short enqueue-window workload on the virtual
CPU rig (2 chips, a few windows, a rebalance) and dumps the registry
the runtime populated — the quickest way to see every ``ck_*`` series a
real run produces.  Without ``--demo`` it prints whatever the current
process registered (empty unless you import this from instrumented
code).

``--url http://host:port/metrics`` switches the source to a LIVE debug
server (``Cores.serve_debug`` / ``CK_DEBUG_PORT``) in another process.
With ``--watch N`` the view re-renders every N
seconds as a top-like per-lane table: bytes moved (with per-interval
rates), fence waits, driver/stream queue depths, the autotuner's chunk
choice, and the lane-health verdict.

Usage::

    python tools/metrics_dump.py --demo            # table
    python tools/metrics_dump.py --demo --prom     # Prometheus text
    python tools/metrics_dump.py --demo --json     # JSON snapshot
    python tools/metrics_dump.py --url http://127.0.0.1:8421/metrics \\
        --watch 2                                  # live lane top
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


_JSONSAFE = None


def _json_safe(o):
    """Delegates to tools/_jsonsafe.py (loaded by file path — this tool
    must run standalone, via `python tools/<name>.py`, AND as an
    importlib-loaded module with no package context)."""
    global _JSONSAFE
    if _JSONSAFE is None:
        import importlib.util

        p = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "_jsonsafe.py")
        spec = importlib.util.spec_from_file_location("ck_tools_jsonsafe", p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _JSONSAFE = mod.json_safe
    return _JSONSAFE(o)


def _demo() -> None:
    """A few enqueue windows on the 2-chip virtual rig — populates the
    balancer, worker, fused, and barrier series."""
    import numpy as np

    from cekirdekler_tpu import ClArray, all_devices
    from cekirdekler_tpu.core.cruncher import NumberCruncher

    src = """
    __kernel void saxpy(__global float* x, __global float* y, float a) {
        int i = get_global_id(0);
        y[i] = y[i] + a * x[i];
    }
    """
    devs = all_devices().cpus()
    cr = NumberCruncher(devs.subset(min(2, len(devs))), src)
    try:
        n = 4096
        x = ClArray(np.arange(n, dtype=np.float32), partial_read=True,
                    read_only=True)
        y = ClArray(np.ones(n, np.float32), partial_read=True)
        cr.enqueue_mode = True
        for _ in range(2):
            for _ in range(8):
                x.next_param(y).compute(cr, 1, "saxpy", n, 64, values=(2.0,))
            cr.barrier()
        cr.enqueue_mode = False
    finally:
        cr.dispose()


def _table(snapshot: dict) -> str:
    lines = []
    for kind in ("counters", "gauges"):
        block = snapshot.get(kind) or {}
        if block:
            lines.append(f"-- {kind}")
            w = max(len(k) for k in block)
            for k in sorted(block):
                lines.append(f"  {k:<{w}}  {block[k]}")
    hists = snapshot.get("histograms") or {}
    if hists:
        lines.append("-- histograms")
        for k in sorted(hists):
            v = hists[k]
            mean = v["sum"] / v["count"] if v["count"] else 0.0
            lines.append(
                f"  {k}  count={v['count']} sum={v['sum']:.6g} "
                f"mean={mean:.6g}"
            )
    return "\n".join(lines) if lines else "(registry empty)"


def _series_label(series: str, key: str) -> str | None:
    m = re.search(r'%s="([^"]*)"' % re.escape(key), series)
    return m.group(1) if m else None


def _lane_view(series: dict, prev: dict | None, dt: float) -> str:
    """The top-like per-lane table from one parsed /metrics poll.
    ``prev``/``dt`` turn cumulative byte counters into interval rates."""
    lanes: dict[str, dict] = {}

    def lane_row(lane: str) -> dict:
        return lanes.setdefault(lane, {})

    def rate(name: str, cur_v: float) -> float | None:
        if prev is None or dt <= 0 or name not in prev:
            return None
        return max(cur_v - prev[name], 0.0) / dt

    for name, v in series.items():
        lane = _series_label(name, "lane")
        if lane is None:
            continue
        row = lane_row(lane)
        if name.startswith("ck_upload_bytes_total"):
            row["up_B"] = v
            row["up_Bps"] = rate(name, v)
        elif name.startswith("ck_download_bytes_total"):
            row["down_B"] = v
            row["down_Bps"] = rate(name, v)
        elif name.startswith("ck_fence_waits_total"):
            row["fences"] = v
        elif name.startswith("ck_fence_seconds_sum"):
            row["fence_s"] = v
        elif name.startswith("ck_driver_queue_depth"):
            row["drvq"] = v
        elif name.startswith("ck_stream_queue_depth"):
            row["strq"] = v
        elif name.startswith("ck_stream_chunk_count"):
            row["chunks"] = v
        elif name.startswith("ck_lane_health_peak"):
            # MUST precede the ck_lane_health test (shared prefix): the
            # peak would otherwise shadow the current verdict and a
            # recovered lane would render degraded forever
            from cekirdekler_tpu.obs.health import score_verdict

            row["peak"] = score_verdict(v)
        elif name.startswith("ck_lane_health"):
            # the one verdict mapping lives in obs.health (jax-free)
            from cekirdekler_tpu.obs.health import score_verdict

            row["health"] = score_verdict(v)

    def fmt_bytes(n):
        if n is None:
            return "-"
        for unit in ("B", "KiB", "MiB", "GiB"):
            if n < 1024 or unit == "GiB":
                return f"{n:.1f}{unit}"
            n /= 1024.0

    hdr = (f"{'lane':>4} {'health':>8} {'peak':>8} {'up':>10} {'up/s':>10} "
           f"{'down':>10} {'down/s':>10} {'fences':>7} {'fence_s':>8} "
           f"{'drvq':>5} {'strq':>5} {'chunks':>6}")
    lines = [hdr]
    for lane in sorted(lanes, key=lambda x: (len(x), x)):
        r = lanes[lane]
        lines.append(
            f"{lane:>4} {r.get('health', '-'):>8} {r.get('peak', '-'):>8} "
            f"{fmt_bytes(r.get('up_B')):>10} {fmt_bytes(r.get('up_Bps')):>10} "
            f"{fmt_bytes(r.get('down_B')):>10} "
            f"{fmt_bytes(r.get('down_Bps')):>10} "
            f"{r.get('fences', 0):>7.0f} {r.get('fence_s', 0.0):>8.3f} "
            f"{r.get('drvq', 0):>5.0f} {r.get('strq', 0):>5.0f} "
            f"{r.get('chunks', '-'):>6}"
        )
    if len(lines) == 1:
        lines.append("(no lane-labeled series yet)")
    return "\n".join(lines)


def _watch(url: str, interval: float, count: int, prom: bool) -> int:
    """Poll a live debug-server /metrics endpoint over HTTP (NOT
    in-process — the whole point is watching another process
    from outside) and re-render.  ``count`` 0 = until interrupted."""
    from cekirdekler_tpu.metrics import parse_prometheus_text

    prev: dict | None = None
    t_prev = 0.0
    n = 0
    while True:
        try:
            with urllib.request.urlopen(url, timeout=10) as r:
                text = r.read().decode()
        except OSError as e:
            print(f"poll failed: {e}", file=sys.stderr)
            return 1
        now = time.time()
        if prom:
            sys.stdout.write(text)
        else:
            parsed = parse_prometheus_text(text)
            stamp = time.strftime("%H:%M:%S", time.localtime(now))
            print(f"-- {stamp}  {url}  "
                  f"({len(parsed['series'])} series)")
            print(_lane_view(parsed["series"], prev, now - t_prev))
            prev, t_prev = parsed["series"], now
        n += 1
        if count and n >= count:
            return 0
        if interval <= 0:
            return 0
        try:
            time.sleep(interval)
        except KeyboardInterrupt:
            return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--prom", action="store_true",
                    help="Prometheus exposition format")
    ap.add_argument("--json", action="store_true", help="JSON snapshot")
    ap.add_argument("--demo", action="store_true",
                    help="run a short instrumented rig workload first")
    ap.add_argument("--url", default=None,
                    help="poll a live debug-server /metrics endpoint over "
                         "HTTP instead of reading in-process")
    ap.add_argument("--watch", type=float, default=None, metavar="N",
                    help="with --url: re-render every N seconds "
                         "(top-like lane view; 0 = one poll)")
    ap.add_argument("--count", type=int, default=0,
                    help="with --watch: stop after this many polls "
                         "(0 = until interrupted)")
    args = ap.parse_args(argv)

    if args.watch is not None and not args.url:
        ap.error("--watch requires --url (it polls a live debug server)")
    if args.url:
        return _watch(args.url, args.watch or 0.0, args.count, args.prom)

    if args.demo:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        _demo()
    from cekirdekler_tpu.metrics import REGISTRY, prometheus_text

    if args.prom:
        sys.stdout.write(prometheus_text())
    elif args.json:
        print(json.dumps(_json_safe(REGISTRY.snapshot()), indent=2,
              sort_keys=True, allow_nan=False))
    else:
        print(_table(REGISTRY.snapshot()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
