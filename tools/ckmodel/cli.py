"""``python -m tools.ckmodel`` — the bounded model checker's CI gate.

Mirrors the ckcheck/ckprove lifecycle exactly: exit 0 = no findings
beyond the (expected-empty) baseline AND no stale entries;
``--update-baseline`` refuses growth without ``--allow-grow``; the
shared provenance header names the commit the ratchet was burned at
(``--explain provenance``).

Two finding families ride one ratchet:

- **model violations** — an invariant from a controller module's
  ``MODEL_INVARIANTS`` refuted by bounded exhaustive exploration, with
  a minimal counterexample trace in the decision-record schema
  (``--explain <fp>`` renders it; ``--save-trace DIR`` spills each as
  a ``ck-decision-log-v1`` jsonl for ``ckreplay verify``/``explain``);
- **purity findings** — a model-checked function reading the clock,
  RNG, or a mutable module global (``tools/ckmodel/purity.py``),
  which would make both the checker and replay-verify unsound.

Usage::

    python -m tools.ckmodel                       # the CI gate
    python -m tools.ckmodel --machine drain       # one machine
    python -m tools.ckmodel --depth 2             # deepen the bounds
    python -m tools.ckmodel --json                # machine-readable
    python -m tools.ckmodel --explain <fp>        # one finding
    python -m tools.ckmodel --save-trace DIR      # spill traces
    python -m tools.ckmodel --update-baseline [--allow-grow]

``CK_MODEL_DEPTH`` is the environment form of ``--depth`` (deepens
tier-1 bounds without editing CI).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "baseline.json")

if REPO not in sys.path:  # direct-script invocation
    sys.path.insert(0, REPO)

from tools.ckcheck.baseline import (  # noqa: E402
    load_baseline,
    load_baseline_doc,
    provenance_note,
    ratchet,
    save_baseline,
)
from tools.ckmodel import purity  # noqa: E402

RULE_DOCS = {
    "model-violation": (
        "Bounded exhaustive exploration of the REAL controller "
        "function refuted a declared MODEL_INVARIANTS property.  The "
        "finding carries a minimal counterexample trace in the "
        "decision-record schema: save it with --save-trace, render it "
        "with `python -m tools.ckreplay explain <trace>`, replay it "
        "with `... verify <trace>`.  Fix the controller (never the "
        "invariant, unless the spec itself was wrong) and pin the "
        "trace as a regression test — the ckcheck PR 7 discipline."),
    "purity": (
        "A model-checked controller function calls the clock/RNG/"
        "filesystem or reads a mutable module global.  Both the model "
        "checker and `ckreplay verify` assume these functions are "
        "pure; an impure read makes every 'bit-identical replay' "
        "claim unsound.  Move the impurity to the stateful wrapper "
        "(the DrainController/AdmissionController layer) and pass the "
        "value in as an argument, or declare an explicit seam in "
        "tools/ckmodel/purity.py with a why."),
}


def analyze(machine: str | None = None, scale: int | None = None):
    """``(findings, report)`` — model violations (+ purity findings)
    and the exploration report."""
    from cekirdekler_tpu.analysis import model

    names = (machine,) if machine else None
    report = model.check_all(names=names, scale=scale)
    findings = list(report["violations"])
    if machine is None:
        findings.extend(purity.run(REPO))
    findings.sort(key=lambda f: (f.path, f.line, f.fingerprint))
    return findings, report


def _render_trace(v) -> str:
    from cekirdekler_tpu.utils.jsonsafe import json_safe

    lines = [f"counterexample ({len(v.trace)} step(s)):"]
    for row in v.trace:
        out = row.get("outputs") or {}
        brief = {k: out[k] for k in
                 ("action", "ranges", "drained", "readmitted", "admit",
                  "reason", "picked", "promoted", "epoch_after")
                 if k in out}
        lines.append(
            f"  seq {row['seq']:>3} {row['kind']:<14} "
            f"{json.dumps(json_safe(brief), default=str, allow_nan=False)[:120]}")
    lines.append(
        "terminal state: "
        + json.dumps(json_safe(v.state_doc), default=str,
                     allow_nan=False)[:400])
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.ckmodel",
        description="bounded exhaustive model checker for the pure "
                    "controller state machines "
                    "(docs/STATIC_ANALYSIS.md)")
    ap.add_argument("--machine", choices=("drain", "elastic", "serve",
                                          "balance", "resilience",
                                          "block"),
                    help="check one machine (default: all six + the "
                         "purity lint)")
    ap.add_argument("--depth", type=int, default=None,
                    help="bound scale (default 1 = tier-1; env "
                         "CK_MODEL_DEPTH)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from current findings "
                         "(refuses NEW findings without --allow-grow)")
    ap.add_argument("--allow-grow", action="store_true",
                    help="permit --update-baseline to add findings")
    ap.add_argument("--explain", metavar="FINGERPRINT",
                    help="print one finding with its counterexample "
                         "trace ('provenance' prints the baseline "
                         "header)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable findings + exploration "
                         "report (exit code semantics unchanged)")
    ap.add_argument("--save-trace", metavar="DIR",
                    help="spill every violation's counterexample as "
                         "DIR/<fingerprint>.jsonl (ck-decision-log-v1 "
                         "— ckreplay verify/explain read them)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline file (default: tools/ckmodel/"
                         "baseline.json)")
    args = ap.parse_args(argv)

    if args.explain == "provenance":
        print(provenance_note(load_baseline_doc(args.baseline)))
        return 0

    if args.update_baseline and args.machine:
        # a partial scan must never rewrite (and thereby truncate) the
        # FULL baseline — other machines' and the purity lint's
        # grandfathered entries would silently vanish
        print("ckmodel: --update-baseline requires a full scan "
              "(drop --machine)")
        return 2

    findings, report = analyze(args.machine, args.depth)
    baseline = load_baseline(args.baseline)
    if args.machine:
        # scope the ratchet to the scanned machine: entries belonging
        # to unscanned machines (path 'model:<other>') or the purity
        # lint are neither stale nor grandfathered in a partial run
        prefix = f"model:{args.machine}"
        baseline = {fp: row for fp, row in baseline.items()
                    if str(row.get("path", "")).startswith(prefix)}
    new, grand, stale = ratchet(findings, baseline)

    if args.save_trace:
        from cekirdekler_tpu.obs.replay import save_counterexample

        os.makedirs(args.save_trace, exist_ok=True)
        for f in findings:
            if hasattr(f, "trace"):
                p = os.path.join(args.save_trace,
                                 f"{f.fingerprint}.jsonl")
                save_counterexample(p, f)
                print(f"ckmodel: trace spilled: {p}")

    if args.explain:
        for f in findings:
            if f.fingerprint.startswith(args.explain):
                print(f.render())
                print()
                doc_key = ("model-violation" if hasattr(f, "trace")
                           else "purity")
                print(RULE_DOCS[doc_key])
                if hasattr(f, "trace"):
                    print()
                    print(_render_trace(f))
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
            print(f"ckmodel: REFUSING to grow the baseline by "
                  f"{len(new)} new finding(s) (pass --allow-grow to "
                  "grandfather deliberately):")
            for f in new:
                print("  " + f.render())
            return 1
        save_baseline(args.baseline, findings, tool="ckmodel")
        print(f"ckmodel: baseline rewritten: {len(findings)} finding(s) "
              f"({len(new)} added, {len(stale)} removed)")
        return 0

    if args.json:
        doc = {
            "new": [f.to_row() for f in new],
            "grandfathered": [f.to_row() for f in grand],
            "stale_baseline": stale,
            "states_explored": report["states_explored"],
            "transitions": report["transitions"],
            "machines": {
                n: {
                    "states_explored": r["states_explored"],
                    "transitions": r["transitions"],
                    "truncated": r["truncated"],
                    "violations": len(r["violations"]),
                    "sub_machines": r["sub_machines"],
                }
                for n, r in report["machines"].items()
            },
        }
        print(json.dumps(doc, indent=1, sort_keys=True, default=str,
                         allow_nan=False))
        return 0 if not new and not stale else 1

    ok = True
    if new:
        ok = False
        print(f"ckmodel: {len(new)} NEW finding(s) (not in baseline):")
        for f in new:
            print("  " + f.render())
        print("  (fix the controller, pin the trace — --explain <fp> "
              "shows the counterexample; --update-baseline "
              "--allow-grow grandfathers deliberately)")
    if stale:
        ok = False
        print(f"ckmodel: {len(stale)} STALE baseline entr"
              f"{'y' if len(stale) == 1 else 'ies'} (finding fixed but "
              "baseline not shrunk — run --update-baseline):")
        for row in stale:
            print(f"  [{row['fingerprint']}] {row.get('path')}:"
                  f"{row.get('line')} {row.get('message', '')[:80]}")
        print("  (" + provenance_note(
            load_baseline_doc(args.baseline)) + ")")
    if ok:
        per = " ".join(
            f"{n}={r['states_explored']}"
            for n, r in report["machines"].items())
        print(f"ckmodel: clean — {report['states_explored']} canonical "
              f"states explored ({per}), every declared invariant "
              f"held; {len(findings)} grandfathered finding(s) remain "
              "in the baseline")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
