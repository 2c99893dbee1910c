"""``python -m tools.ckcheck`` — the repo-wide concurrency & hot-path
static analyzer with a ratcheted baseline (docs/STATIC_ANALYSIS.md).

Import-free with respect to the analyzed code (pure ``ast``, the
``lint_obs`` contract): runs anywhere, including rigs where jax is
broken.  Exit 0 = no findings beyond the checked-in baseline AND no
stale baseline entries; anything else exits 1 with the findings.

Usage::

    python -m tools.ckcheck                  # the CI gate
    python -m tools.ckcheck --explain <fp>   # one finding, full detail
    python -m tools.ckcheck --update-baseline [--allow-grow]
    python -m tools.ckcheck --json           # machine-readable dump
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from .baseline import (
    load_baseline,
    load_baseline_doc,
    provenance_note,
    ratchet,
    save_baseline,
)
from .model import scan_package
from .passes import AnalyzerConfig, run_passes

__all__ = ["main", "analyze_repo", "repo_config", "REPO"]

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "baseline.json")

#: The declared hot set: the fused deferral path, the driver-queue
#: submit paths, the flight-ring append, the tracer record paths, and
#: the device-capture correlation marks (every ladder/chunk launch
#: calls begin/end behind a plain `.enabled` guard — annotation work
#: must stay behind that guard and never grow a lock or a registry
#: get-or-create).  Anything these reach (minus `# ckcheck: cold`
#: window boundaries) must obey the cached-handle / allowlisted-lock /
#: no-alloc-telemetry discipline.
HOT_ROOTS = (
    "core.window.Window.defer",
    "core.worker._DriverQueue.submit",
    "core.worker.Worker.dispatch_async",
    "core.worker.Worker.stream_dispatch_async",
    "obs.flight.FlightRecorder.event",
    # the request-lifecycle append (ISSUE 19): always on, rides every
    # serve submit/dispatch — GIL-atomic deque append, no locks, no
    # registry traffic, same budget class as FlightRecorder.event
    "obs.reqtrace.ReqTrace.event",
    "trace.spans.Tracer.t0",
    "trace.spans.Tracer.record",
    "trace.spans.Tracer.instant",
    "trace.device.DeviceMarks.begin",
    "trace.device.DeviceMarks.end",
    # the serving tier's submit→coalesce path (ISSUE 11): every client
    # request pays submit; the decision-record inputs stay behind
    # DECISIONS.enabled, tenant metric handles are cached at first
    # sight, and only the allowlisted frontend/table locks may be taken
    "serve.frontend.ServeFrontend.submit",
    "serve.admission.AdmissionController.check",
    # the circuit-breaker check on the submit path (ISSUE 15): one
    # board-lock dict hit for breakerless keys; transitions use
    # handles cached at board construction and record decisions only
    # behind DECISIONS.enabled
    "serve.resilience.BreakerBoard.admit",
    # the fault-injection plane (ISSUE 13): fire() is reached from the
    # driver-queue submit path — every instrumented site guards with
    # `if FAULTS.enabled:` and the per-point metric handles are cached
    # at arm time, so the disabled plane costs one attribute read
    "utils.faultinject.FaultPlane.fire",
    "utils.faultinject.FaultPlane.delay_s",
    "utils.faultinject.FaultPlane.raise_if_fired",
    # the block autotuner's choice path (ISSUE 16): sits on the flash
    # default-argument path — metric handles cached at construction,
    # the ProfileStore read happens once per key ever (outside the
    # mutex), and decision/flight records emit only on a choice CHANGE
    # behind the recorders' enabled flags
    "core.blocktuner.BlockTuner.choose",
    # the fabric routing path (ISSUE 17): every cluster request pays
    # route() + submit() — the pure route_decision core allocates only
    # small tuples/dicts, metric handles are cached at construction,
    # diversion flight events and route decision records emit behind
    # the recorders' enabled flags, and only the router/fabric locks
    # below may be taken
    "serve.fabric.ShardRouter.route",
    "serve.fabric.ServeFabric.submit",
)

#: Locks the hot path may take: the scheduler lock + fused-window mutex
#: (one uncontended acquisition per deferral is the documented budget),
#: the driver queue's condition (submit backpressure IS its job), and
#: the per-metric update lock (exact counters are the registry's
#: design point 2).
HOT_LOCK_ALLOW = (
    "core.window.Window.lock",
    "core.window.Window._mu",
    "core.worker._DriverQueue._cond",
    "metrics.registry._Metric._lock",
    # serving submit path: ONE frontend condition guards the whole
    # admit→enqueue transition (exact quota counts under contention
    # are the contract), with the tenant table's and admission
    # controller's small-state locks nested inside it — each held for
    # a few dict operations per request, the documented budget
    "serve.frontend.ServeFrontend._mu",
    "serve.tenants.TenantTable._mu",
    "serve.admission.AdmissionController._mu",
    # fault plane: taken ONLY when an armed clause matches the point —
    # test/chaos rigs; the disabled fast path never reaches it
    "utils.faultinject.FaultPlane._mu",
    # breaker board: one uncontended acquisition per submit (a dict
    # miss for keys with no breaker state), nested inside the frontend
    # condition — the documented budget
    "serve.resilience.BreakerBoard._mu",
    # block tuner: a few short value-copy critical sections per choose
    # (snapshot walls / apply choice), never held across the store
    # read or the recorders — the TransferTuner discipline
    "core.blocktuner.BlockTuner._mu",
    # fabric route/submit: one short roster+health snapshot under the
    # router lock, one in-flight bookkeeping write under the fabric
    # lock — neither is held across a shard submit or any recorder
    "serve.fabric.ShardRouter._mu",
    "serve.fabric.ServeFabric._mu",
    # the roster snapshot itself: ShardRouter.route copies {epoch,
    # members} under the membership's own lock, a dict copy a request
    # (seen since the analyzer follows collaborators handed to a
    # constructor through an annotated parameter)
    "cluster.elastic.Membership._mu",
    # retry budgets (reached from the fabric re-route path): a couple
    # of dict reads/writes per preempted request under one small-state
    # lock — preemption recovery, not the steady-state submit path
    "serve.resilience.RetryBudgets._mu",
)


def repo_config() -> AnalyzerConfig:
    return AnalyzerConfig(
        hot_roots=HOT_ROOTS,
        hot_lock_allow=HOT_LOCK_ALLOW,
        span_vocab=("trace.spans", "SPAN_KINDS"),
        event_vocab=("obs.flight", "EVENT_KINDS"),
        decision_vocab=("obs.decisions", "DECISION_KINDS"),
        req_vocab=("obs.reqtrace", "REQ_EVENT_KINDS"),
    )


def _repo_extra_paths() -> list:
    """The standalone tools (invariant-pass coverage); the
    analyzer's own package is excluded — it lints itself via the
    package scan only when listed here, which it is."""
    out = []
    tools_dir = os.path.join(REPO, "tools")
    for fn in sorted(os.listdir(tools_dir)):
        if fn.endswith(".py"):
            out.append(os.path.join(tools_dir, fn))
    for sub in ("ckcheck", "ckmodel"):
        ck = os.path.join(tools_dir, sub)
        if not os.path.isdir(ck):
            continue
        for fn in sorted(os.listdir(ck)):
            if fn.endswith(".py"):
                out.append(os.path.join(ck, fn))
    return [p for p in out if os.path.isfile(p)]


def analyze_repo(root: str | None = None):
    """(findings, package) for the live tree."""
    root = root or os.path.join(REPO, "cekirdekler_tpu")
    pkg = scan_package(
        root, pkg_name="cekirdekler_tpu",
        extra_paths=tuple(_repo_extra_paths()), repo_root=REPO)
    return run_passes(pkg, repo_config()), pkg


RULE_DOCS = {
    "order-cycle": (
        "Two code paths acquire the named locks in opposite orders; if the "
        "paths ever interleave across threads, each holds what the other "
        "wants — classic ABBA deadlock.  Fix: pick ONE order (document it "
        "at the lock definitions) and restructure the second path."),
    "reacquire": (
        "A flow that already holds a non-reentrant lock reaches a site "
        "that acquires it again — it blocks on itself forever (the PR 6 "
        "shape: snapshot() under the tracer lock calling "
        "_sync_dropped_metric, which takes the same lock).  Fix: split a "
        "_locked variant that asserts the caller holds the lock, or make "
        "the outer caller release first."),
    "unguarded-read": (
        "An attribute whose writes are consistently locked is READ with "
        "no common lock — the read can observe stale or half-updated "
        "state.  Often deliberate in this repo ('racy read, reporting "
        "only'): annotate `# ckcheck: ok <why>` when so, or take the "
        "writers' lock / snapshot under it when the read feeds a "
        "decision."),
    "mixed-guard": (
        "An attribute is written under a lock at some sites and touched "
        "with no common lock at others — the unlocked read-modify-write "
        "can lose the locked writer's update (the seed-era "
        "enqueue/rebalance lost-update class).  Fix: take the same lock "
        "at every site, or annotate `# ckcheck: ok <why>` when the "
        "lock-free access is a deliberate, documented design."),
    "get-or-create": (
        "REGISTRY.counter/gauge/histogram is get-or-create: a dict lookup "
        "plus a possible registry lock per call.  On the hot set this is "
        "the exact finding PRs 4-6 fixed four times by hand: cache the "
        "handle on the owning object at construction."),
    "hot-lock": (
        "A hot-path function takes a lock outside the allowlist — every "
        "deferral/submit would serialize on it.  Move the work to a "
        "window boundary (annotate the boundary `# ckcheck: cold`) or "
        "add the lock to the allowlist with a budget argument."),
    "telemetry-alloc": (
        "Arguments of a tracer/flight call are computed (f-string, "
        "concat, call) before the callee's disabled check — disabled "
        "telemetry still allocates per call.  Guard the site with "
        "`if TRACER.enabled:` / `if FLIGHT.enabled:`."),
    "headline-last": (
        "Artifact dicts must keep 'headline' as the final key: a reader "
        "that keeps only the tail of a tool's output recovers the "
        "headline from that tail."),
    "undeclared-kind": (
        "A span/flight-event/decision/request-lifecycle kind is "
        "emitted that is not declared in SPAN_KINDS / EVENT_KINDS / "
        "DECISION_KINDS / REQ_EVENT_KINDS — the "
        "vocabulary tuples are the contract lint_obs checks the "
        "documentation against; an undeclared kind is invisible to the "
        "doc lint."),
    "json-unsafe": (
        "json.dumps serializes float('inf')/nan as bare Infinity/NaN "
        "(invalid per RFC 8259 — the PR 6 /healthz consumer-breaking "
        "bug), and raises TypeError on numpy scalars, killing the whole "
        "export.  Route the payload through "
        "cekirdekler_tpu.utils.jsonsafe.json_safe(...) or pass "
        "allow_nan=False (fail loudly, never emit invalid JSON)."),
    "unbounded-blocking": (
        "A zero-argument .join()/.wait()/.get() blocks FOREVER when "
        "its counterpart thread died or its sentinel never arrives — "
        "the shutdown-hang shape (a serve dispatcher or driver queue "
        "stuck in close()).  Fix: pass a timeout and re-check the "
        "predicate in a loop, or annotate `# ckcheck: ok <why>` when "
        "unbounded blocking IS the design (sentinel-terminated daemon "
        "loops, user-triggered gates)."),
    "syntax-error": "The file does not parse; nothing in it was analyzed.",
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.ckcheck",
        description="concurrency & hot-path static analyzer "
                    "(docs/STATIC_ANALYSIS.md)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from current findings "
                         "(refuses NEW findings without --allow-grow)")
    ap.add_argument("--allow-grow", action="store_true",
                    help="permit --update-baseline to add findings")
    ap.add_argument("--explain", metavar="FINGERPRINT",
                    help="print one finding with its rule documentation")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable findings dump (exit code "
                         "semantics unchanged)")
    ap.add_argument("--root", default=None,
                    help="package root to scan (default: cekirdekler_tpu)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline file (default: tools/ckcheck/"
                         "baseline.json)")
    args = ap.parse_args(argv)

    if args.explain == "provenance":
        # derived solely from the baseline file — never pay the scan
        print(provenance_note(load_baseline_doc(args.baseline)))
        return 0

    findings, _pkg = analyze_repo(args.root)
    baseline = load_baseline(args.baseline)
    new, grand, stale = ratchet(findings, baseline)

    if args.explain:
        for f in findings:
            if f.fingerprint.startswith(args.explain):
                print(f.render())
                print()
                print(RULE_DOCS.get(f.rule, "(no rule documentation)"))
                status = ("grandfathered in baseline"
                          if f.fingerprint in baseline else
                          "NEW (not in baseline)")
                print(f"\nstatus: {status}")
                return 0
        print(f"no finding with fingerprint {args.explain!r}",
              file=sys.stderr)
        return 1

    if args.update_baseline:
        if new and not args.allow_grow:
            print(f"ckcheck: REFUSING to grow the baseline by "
                  f"{len(new)} new finding(s) (pass --allow-grow to "
                  "grandfather deliberately):")
            for f in new:
                print("  " + f.render())
            return 1
        save_baseline(args.baseline, findings, tool="ckcheck")
        print(f"ckcheck: baseline rewritten: {len(findings)} finding(s) "
              f"({len(new)} added, {len(stale)} removed)")
        return 0

    if args.json:
        print(json.dumps({
            "new": [f.to_row() for f in new],
            "grandfathered": [f.to_row() for f in grand],
            "stale_baseline": stale,
        }, indent=1, sort_keys=True, allow_nan=False))
        return 0 if not new and not stale else 1

    ok = True
    if new:
        ok = False
        print(f"ckcheck: {len(new)} NEW finding(s) (not in baseline):")
        for f in new:
            print("  " + f.render())
        print("  (fix them, annotate `# ckcheck: ok <why>`, or "
              "--update-baseline --allow-grow to grandfather)")
    if stale:
        ok = False
        print(f"ckcheck: {len(stale)} STALE baseline entr"
              f"{'y' if len(stale) == 1 else 'ies'} (finding fixed but "
              "baseline not shrunk — run --update-baseline):")
        for row in stale:
            print(f"  [{row['fingerprint']}] {row.get('path')}:"
                  f"{row.get('line')} {row.get('message', '')[:80]}")
        print("  (" + provenance_note(
            load_baseline_doc(args.baseline)) + ")")
    if ok and not args.json:
        print(f"ckcheck: clean — {len(findings)} grandfathered finding(s) "
              f"remain in the baseline (ratchet: this number only goes "
              "down)")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
