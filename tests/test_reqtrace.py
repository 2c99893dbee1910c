"""Request-lifecycle tracing (obs/reqtrace.py): ring semantics + the
overhead pins, the telescoping phase fold and its coverage contract,
tail-anatomy percentile decomposition, per-request Perfetto tracks and
their round-trip exclusion from host spans, the 128-client live
coverage pin (phase sums explain >= 95% of every measured wall), the
/servez windowed-latency two-regime snapshot, /reqz, and the
rid-filtered decision explain.

The inc kernel adds exactly 1.0f per request — the test_serve.py
bit-exactness discipline — so the live pin runs a REAL contended
frontend, not a mock timeline."""

import inspect
import json
import threading
import time
import urllib.request
from functools import partial

import numpy as np
import pytest

from cekirdekler_tpu import ClArray
from cekirdekler_tpu.core import NumberCruncher
from cekirdekler_tpu.hardware import platforms
from cekirdekler_tpu.obs.reqtrace import (
    QUEUE_PHASES,
    REQ_EVENT_KINDS,
    REQTRACE,
    TERMINAL_KINDS,
    ReqTrace,
    anatomy_table,
    fold_phases,
    phase_fracs,
    request_chrome_events,
    reqz_payload,
    slowest_requests,
    tail_anatomy,
    tenant_percentiles,
)
from cekirdekler_tpu.serve import ServeFrontend, ServeJob

INC = """
__kernel void inc(__global float* a) {
    int i = get_global_id(0);
    a[i] = a[i] + 1.0f;
}
"""


@pytest.fixture(scope="module")
def devs():
    return platforms().cpus()


# ---------------------------------------------------------------------------
# recorder: ring semantics, mint uniqueness, the overhead pins
# ---------------------------------------------------------------------------

class _NoopShape:
    """Same call shape as ReqTrace.event with the body removed — the
    interpreter's bound-method + kwargs floor (test_obs.py idiom)."""

    def event(self, rid, kind, **fields):
        pass


def _best_per_call(fn, n=20_000, trials=10) -> float:
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        best = min(best, (time.perf_counter() - t0) / n)
    return best


def _best_pair(fn_floor, fn_probe, n=100_000, trials=10):
    """Interleaved best-of (test_obs.py): both sides get the same
    scheduler weather, best-of keeps the clean trials."""
    best_f = best_p = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(n):
            fn_floor()
        best_f = min(best_f, (time.perf_counter() - t0) / n)
        t0 = time.perf_counter()
        for _ in range(n):
            fn_probe()
        best_p = min(best_p, (time.perf_counter() - t0) / n)
    return best_f, best_p


def test_reqtrace_ring_bounded_oldest_first():
    rt = ReqTrace(capacity=16)
    for i in range(40):
        rt.event(f"r{i}", "queued", i=i)
    events = rt.snapshot()
    assert len(events) == 16
    assert rt.total_recorded == 40
    assert [e.fields["i"] for e in events] == list(range(24, 40))
    rt.clear()
    assert rt.snapshot() == [] and rt.total_recorded == 0


def test_mint_is_unique_under_contention():
    rt = ReqTrace()
    out: list = []
    mu = threading.Lock()

    def worker():
        local = [rt.mint() for _ in range(500)]
        with mu:
            out.extend(local)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(out) == len(set(out)) == 4000
    assert all(r.startswith("r") and "-" in r for r in out)


def test_disabled_reqtrace_event_overhead_under_budget():
    """The ISSUE 19 pin, same family as the flight recorder's: a
    disabled request event costs < 100 ns marginal over the identical
    no-op call, and < 1 µs absolute."""
    rt = ReqTrace()
    rt.enabled = False
    noop = _NoopShape()
    floor, per = _best_pair(
        partial(noop.event, "r1", "probe"), partial(rt.event, "r1", "probe"))
    net = per - floor
    assert net < 100e-9, (
        f"disabled reqtrace event adds {net*1e9:.0f} ns over the call "
        f"floor ({per*1e9:.0f} ns total, floor {floor*1e9:.0f} ns)")
    assert per < 1e-6, f"disabled absolute {per*1e9:.0f} ns >= 1 µs"
    assert rt.total_recorded == 0


def test_enabled_reqtrace_append_under_microsecond():
    """Enabled is one clock read + one tuple build + one GIL-atomic
    deque append: < 1 µs per append, best-of — the always-on budget the
    serve submit path rides."""
    rt = ReqTrace(capacity=1024)
    per = _best_per_call(partial(rt.event, "r1", "queued"))
    assert per < 1e-6, f"enabled reqtrace append costs {per*1e9:.0f} ns"


def test_fused_defer_hot_path_has_zero_reqtrace_code():
    """The deepest hot path stays untouched: request-lifecycle stamps
    live at the SERVE layer (submit/coalesce/dispatch), never inside
    the fused deferral fast path."""
    from cekirdekler_tpu.core.window import Window

    src = inspect.getsource(Window.defer) + inspect.getsource(Window.route)
    assert "reqtrace" not in src.lower()
    assert "REQTRACE" not in src


# ---------------------------------------------------------------------------
# the pure fold: telescoping phases, terminal-chain rule, coverage
# ---------------------------------------------------------------------------

def _chain_a():
    return [
        (100.000, "rA", "admitted", {"wait_s": 0.005, "tenant": "tA"}),
        (100.001, "rA", "queued", {}),
        (100.003, "rA", "coalesce-wait", {}),
        (100.004, "rA", "dispatched", {}),
        (100.010, "rA", "device", {}),
        (100.011, "rA", "resolved", {"latency_s": 0.016}),
    ]


def test_fold_phases_telescopes_gaps_onto_the_closing_kind():
    (rec,) = fold_phases(_chain_a())
    assert rec["rid"] == "rA" and rec["tenant"] == "tA"
    assert rec["outcome"] == "resolved"
    assert rec["phases_s"]["admitted"] == pytest.approx(0.005)  # lead wait
    assert rec["phases_s"]["queued"] == pytest.approx(0.001)
    assert rec["phases_s"]["coalesce-wait"] == pytest.approx(0.002)
    assert rec["phases_s"]["dispatched"] == pytest.approx(0.001)
    assert rec["phases_s"]["device"] == pytest.approx(0.006)
    assert rec["phases_s"]["resolved"] == pytest.approx(0.001)
    # wall prefers the terminal event's measured latency_s, and the
    # telescoped phases cover it exactly here
    assert rec["wall_s"] == pytest.approx(0.016)
    assert rec["coverage"] == pytest.approx(1.0)
    assert rec["kinds"] == ["admitted", "queued", "coalesce-wait",
                            "dispatched", "device", "resolved"]


def test_fold_phases_accepts_wire_rows_and_dicts():
    """The three transports (ReqEvent, [t, rid, kind, fields] off the
    _fabric_worker wire, /reqz dict) fold identically."""
    as_tuples = fold_phases(_chain_a())
    as_lists = fold_phases([list(e) for e in _chain_a()])
    as_dicts = fold_phases([
        {"t": t, "rid": rid, "kind": kind, "fields": f}
        for t, rid, kind, f in _chain_a()])
    assert as_tuples == as_lists == as_dicts


def test_fold_phases_terminal_chain_rule():
    """A mid-chain `failed` followed by a reroute hop is NOT an
    outcome — the chain continues on a survivor; only a chain ENDING
    in resolved/failed is terminal."""
    hop = [
        (10.0, "rB", "admitted", {"wait_s": 0.0}),
        (10.1, "rB", "failed", {"latency_s": 0.1}),
        (10.2, "rB", "diverted", {}),
        (10.3, "rB", "rerouted", {}),
    ]
    (rec,) = fold_phases(hop)
    assert rec["outcome"] is None
    assert rec["wall_s"] == pytest.approx(0.3)  # stamp extent fallback
    done = hop + [
        (10.4, "rB", "admitted", {}),
        (10.5, "rB", "resolved", {"latency_s": 0.5}),
    ]
    (rec,) = fold_phases(done)
    assert rec["outcome"] == "resolved"
    assert rec["wall_s"] == pytest.approx(0.5)
    # the whole cross-shard story stays one record
    assert rec["kinds"] == ["admitted", "failed", "diverted", "rerouted",
                            "admitted", "resolved"]


def test_tail_anatomy_nearest_rank_and_phase_fracs():
    events = []
    for i in range(100):
        wall = (i + 1) * 1e-3
        events.append((float(i), f"r{i:03d}", "admitted",
                       {"wait_s": 0.0, "tenant": "tA"}))
        events.append((float(i) + wall, f"r{i:03d}", "resolved",
                       {"latency_s": wall}))
    records = fold_phases(events)
    doc = tail_anatomy(records)
    assert doc["count"] == 100
    # nearest-rank over 100 sorted walls: p50 -> index 50, p99 -> 98
    assert doc["pcts"]["p50"]["wall_ms"] == pytest.approx(51.0)
    assert doc["pcts"]["p99"]["wall_ms"] == pytest.approx(99.0)
    assert doc["pcts"]["p99"]["rid"] == "r098"
    assert doc["mean"]["wall_ms"] == pytest.approx(50.5)
    (rec_a,) = fold_phases(_chain_a())
    fr = phase_fracs(rec_a)
    assert fr["queue_frac"] == pytest.approx(0.008 / 0.016)
    assert fr["device_frac"] == pytest.approx(0.006 / 0.016)
    assert set(QUEUE_PHASES) == {"admitted", "queued", "coalesce-wait"}
    # empty guard
    assert phase_fracs({}) == {"queue_frac": 0.0, "device_frac": 0.0}


def test_tenant_percentiles_and_slowest():
    events = []
    for i, tenant in enumerate(["tA", "tB"] * 5):
        wall = (i + 1) * 1e-3
        events.append((float(i), f"r{i}", "admitted",
                       {"wait_s": 0.0, "tenant": tenant}))
        events.append((float(i) + wall, f"r{i}", "resolved",
                       {"latency_s": wall}))
    records = fold_phases(events)
    per = tenant_percentiles(records)
    assert per["tA"]["count"] == per["tB"]["count"] == 5
    assert per["tB"]["p99_ms"] == pytest.approx(10.0)
    slow = slowest_requests(records, n=3)
    assert [r["rid"] for r in slow] == ["r9", "r8", "r7"]


def test_anatomy_table_renders_every_phase_column():
    doc = tail_anatomy(fold_phases(_chain_a()))
    text = anatomy_table(doc)
    for kind in ("admitted", "coalesce-wait", "device", "resolved"):
        assert kind in text
    assert "cover" in text
    assert anatomy_table({}) == \
        "tail anatomy: no completed requests recorded"


def test_reqz_payload_shape_from_explicit_events():
    doc = reqz_payload(events=_chain_a())
    assert doc["requests"] == 1 and doc["events"] == 6
    assert doc["anatomy"]["count"] == 1
    assert doc["slowest"][0]["rid"] == "rA"
    assert doc["tenants"]["tA"]["count"] == 1
    assert doc["recent"][0]["kinds"][-1] == "resolved"


# ---------------------------------------------------------------------------
# Perfetto tracks: one thread per rid, round-trip exclusion
# ---------------------------------------------------------------------------

def test_request_chrome_events_one_track_per_rid():
    events = _chain_a() + [
        (100.002, "rB", "admitted", {"wait_s": 0.001}),
        (100.006, "rB", "resolved", {"latency_s": 0.005}),
    ]
    out = request_chrome_events(events)
    slices = [e for e in out if e.get("ph") == "X"]
    assert all(e["cat"] == "ck-req" for e in slices)
    assert all(e["args"]["rid"] in ("rA", "rB") for e in slices)
    # one tid per rid, stable across its slices
    tids = {}
    for e in slices:
        tids.setdefault(e["args"]["rid"], set()).add(e["tid"])
    assert all(len(v) == 1 for v in tids.values())
    assert tids["rA"] != tids["rB"]
    # the lead wait_s slice ENDS at the first stamp
    lead = min((e for e in slices if e["args"]["rid"] == "rA"),
               key=lambda e: e["ts"])
    assert lead["name"] == "admitted"
    assert lead["dur"] == pytest.approx(0.005 * 1e6)


def test_unified_trace_carries_req_tracks_and_split_ignores_them():
    from cekirdekler_tpu.trace.device import (
        split_unified_trace,
        unified_chrome_trace,
    )

    doc = unified_chrome_trace([], None, req_events=_chain_a())
    req = [e for e in doc["traceEvents"] if e.get("cat") == "ck-req"]
    assert req, "request tracks missing from the unified trace"
    spans, ops = split_unified_trace(doc)
    assert spans == [] and ops == []  # ck-req never masquerades as host


# ---------------------------------------------------------------------------
# /servez windowed latency: the two-regime snapshot
# ---------------------------------------------------------------------------

def test_window_latency_shows_the_current_regime():
    """512 slow walls followed by 512 fast ones: the last-N window
    reports the FAST regime while a cumulative mean would still be
    dominated by the slow one — the reason /servez carries the window
    next to the lifetime tenant accounting."""
    from cekirdekler_tpu.serve.frontend import _window_latency

    values = [0.100] * 512 + [0.001] * 512
    doc = _window_latency(values, window=512)
    assert doc["count"] == 512
    assert doc["p50_ms"] == pytest.approx(1.0, rel=0.01)
    assert doc["p99_ms"] == pytest.approx(1.0, rel=0.01)
    # flip the regimes: the window sees the slow tail instead
    doc = _window_latency(list(reversed(values)), window=512)
    assert doc["p50_ms"] == pytest.approx(100.0, rel=0.01)
    assert _window_latency([])["count"] == 0
    assert _window_latency([])["p50_ms"] is None


# ---------------------------------------------------------------------------
# the live pin: 128 contended clients, coverage >= 0.95 per request
# ---------------------------------------------------------------------------

def test_live_128_clients_phase_sums_cover_the_wall(devs):
    """The acceptance pin: under a 128-client contended run every
    completed request's telescoped phase sum explains >= 95% of its
    measured wall — no unexplained milliseconds — and the live
    surfaces (/reqz, the /servez latency window) see the run."""
    n = 2048
    cr = NumberCruncher(devs.subset(2), INC)
    a = ClArray(np.zeros(n, np.float32), name="cov")
    a.partial_read = True
    job = ServeJob(params=[a], kernels=["inc"], compute_id=7300,
                   global_range=n, local_range=64)
    fe = ServeFrontend(cr, max_batch=256, gather_window_s=0.002,
                       name="covpin")
    requests_each = 2
    t_wall0 = time.time()
    errs: list = []
    try:
        def client(tenant):
            for _ in range(requests_each):
                try:
                    fe.call(tenant, job, timeout=60.0)
                except Exception as e:  # noqa: BLE001 - assert below
                    errs.append(e)

        threads = [threading.Thread(target=client, args=(f"t{i % 4}",))
                   for i in range(128)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
        assert not errs, errs[:3]
        # wall-clock bound: only THIS run's events (REQTRACE is
        # process-global and other tests may have written to it)
        events = [e for e in REQTRACE.snapshot() if e.t >= t_wall0]
        records = [r for r in fold_phases(events)
                   if r["outcome"] == "resolved"]
        assert len(records) >= 128 * requests_each
        bad = [(r["rid"], r["coverage"]) for r in records
               if r["coverage"] < 0.95]
        assert not bad, (
            f"{len(bad)}/{len(records)} requests have phase sums "
            f"covering < 95% of their wall: {bad[:5]}")
        # every request's story uses the declared vocabulary only
        assert {k for r in records for k in r["kinds"]} <= \
            set(REQ_EVENT_KINDS)
        doc = tail_anatomy(records)
        assert doc["count"] == len(records)
        assert doc["pcts"]["p99"]["coverage"] >= 0.95
        fr = phase_fracs(next(r for r in records
                              if r["rid"] == doc["pcts"]["p99"]["rid"]))
        assert 0.0 <= fr["queue_frac"] <= 1.0 + 1e-9
        assert 0.0 <= fr["device_frac"] <= 1.0 + 1e-9
        # the /servez windowed latency saw this run
        lat = fe.stats()["latency"]
        assert lat["count"] >= 256 and lat["p50_ms"] > 0
        # /reqz live over HTTP
        srv = cr.serve_debug(port=0)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/reqz?slow=3", timeout=10) as r:
            body = json.loads(r.read())
        assert body["enabled"] is True
        assert body["requests"] >= len(records)
        assert len(body["slowest"]) == 3
        assert body["anatomy"]["count"] >= len(records)
    finally:
        fe.close()
        cr.dispose()


# ---------------------------------------------------------------------------
# rid-filtered decision explain (ckreplay explain --rid)
# ---------------------------------------------------------------------------

def test_explain_rid_matches_all_three_input_shapes():
    """The rid appears in decision inputs three ways — scalar `rid`
    (admission/route/retry), flat `rids` (containment), nested
    `groups[i].rids` (coalesce) — and explain_rid finds every one,
    excluding other rids' decisions."""
    from cekirdekler_tpu.obs.replay import explain_rid

    records = [
        {"kind": "admission", "seq": 1, "t": 1.0,
         "inputs": {"rid": "rX", "tenant": "tA"},
         "outputs": {"admit": True}},
        {"kind": "coalesce", "seq": 2, "t": 2.0,
         "inputs": {"groups": [{"key": "g0", "rids": ["rQ", "rX"]}]},
         "outputs": {"picked": ["g0"]}},
        {"kind": "containment", "seq": 3, "t": 3.0,
         "inputs": {"rids": ["rX", "rY"]},
         "outputs": {"mode": "bisect"}},
        {"kind": "route", "seq": 4, "t": 4.0,
         "inputs": {"rid": "rZ"}, "outputs": {"shard": "m1"}},
    ]
    doc = explain_rid(records, "rX")
    assert doc["rid"] == "rX" and doc["decisions"] == 3
    assert doc["kinds"] == {"admission": 1, "coalesce": 1,
                            "containment": 1}
    assert [s["seq"] for s in doc["steps"]] == [1, 2, 3]
    assert explain_rid(records, "rZ")["decisions"] == 1
    assert explain_rid(records, "r-nowhere")["decisions"] == 0


def test_ckreplay_render_explain_rid():
    from cekirdekler_tpu.obs.replay import explain_rid
    from tools.ckreplay import render_explain_rid

    doc = explain_rid([
        {"kind": "admission", "seq": 1, "t": 1.0,
         "inputs": {"rid": "rX"},
         "outputs": {"admit": False, "reason": "queue-full"}},
    ], "rX")
    text = render_explain_rid(doc)
    assert "rX" in text and "admission" in text and "queue-full" in text
