#!/usr/bin/env python
"""Bench regression sentinel: diff the ``BENCH_r*.json`` trajectory on
headline keys and fail LOUDLY on silent regressions and starved
sections.

The failure mode this closes (ISSUE 4): the bench starved a promised
section two rounds running and nothing noticed — a ``null`` in the
artifact reads the same as "never promised".  And a headline number can
drop 30% between rounds with no gate anywhere.  This tool is that gate:

- **Headline diffs, noise-aware.**  Each watched key carries a
  direction and a relative-tolerance floor; when >= 3 historical
  artifacts carry the key, the tolerance widens to ``NOISE_K`` x the
  trajectory's coefficient of variation (host-clock keys on a shared
  machine wander round to round — a fixed 10% gate would cry wolf; a
  key that's historically stable keeps the tight floor).
- **null is a verdict, not a shrug.**  A watched key that the baseline
  carries but the candidate nulls is a HARD failure, with the section
  scheduler's starvation reason attached (bench.py writes
  ``{"null_reason": ..., "budget_spent_s": ...}`` records and an
  ``errors`` map — both are searched).
- **Artifact-format tolerant.**  Driver artifacts are
  ``{"n", "cmd", "rc", "tail", "parsed"}`` where ``tail`` holds only
  the LAST 2000 chars of output; the headline block prints last
  precisely so it survives that truncation — ``extract_tail_object``
  recovers ``headline``/``errors`` from the truncated tail by balanced-
  brace scanning.  Raw ``bench.py`` output lines and already-parsed
  dicts load too.

- **Behavior drift is a sentinel failure too.**  Every artifact embeds
  the decision-log replay-verify verdict (``headline.replay_ok`` —
  bench.py re-executes the run's recorded controller decisions through
  ``obs/replay.py`` and asserts bit-identical outputs); a candidate
  carrying ``replay_ok: false`` hard-fails exactly like a starved key,
  so a balancer edit that silently changes decisions becomes a named
  failure, not a perf mystery attributed to the hardware.

  The same gate covers ``headline.model_ok`` (ISSUE 14): bench.py
  also runs the bounded model checker (``tools/ckmodel``) over the
  controller state machines, and an artifact whose controllers refute
  a declared ``MODEL_INVARIANTS`` property hard-fails identically.

Exit codes: 0 = healthy, 2 = headline regression, 3 = starved/null
watched key OR replay-verify drift OR model-check drift (all nonzero
— CI gates on any nonzero).

Usage::

    python tools/regress.py --against BENCH_r05.json [--candidate F]
    python tools/regress.py --against BENCH_r05.json --json
    python tools/regress.py --history        # per-key trajectory table

With no ``--candidate``, the newest ``BENCH_r*.json`` other than
``--against`` is the candidate.  ``bench.py`` also runs this in-process
as an epilogue (:func:`bench_epilogue`) so every fresh artifact carries
its own verdict against the previous round.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

__all__ = [
    "WATCHED_KEYS",
    "extract_tail_object",
    "load_headline",
    "diff_headlines",
    "bench_epilogue",
    "history_table",
    "no_trajectory_message",
    "main",
]

#: (headline key, aliases in older rounds, direction, rel-tol floor).
#: Direction "higher" = bigger is better; a drop beyond tolerance is a
#: regression (improvements never fail).
WATCHED_KEYS = (
    ("flash_T8192_mfu_default", (), "higher", 0.10),
    ("flash_T8192_speedup_highest", (), "higher", 0.15),
    ("nbody_e2e_enqueue_gpairs", ("nbody_e2e_gpairs",), "higher", 0.15),
    ("dispatch_floor_collapse", (), "higher", 0.20),
    # realized read/compute/write overlap of the balanced row (since
    # ISSUE 5 the STREAMED plain path); named overlap_fraction_raw in
    # the pre-ceiling rounds (r2-r3 bench)
    ("overlap_balanced_raw", ("overlap_fraction_raw",), "higher", 0.15),
    ("mandelbrot_mpix", (), "higher", 0.10),
    ("vs_tuned_loop", (), "higher", 0.10),
    ("repeat_mode_mpix", (), "higher", 0.10),
    # serving tier (ISSUE 11, bench section "serving"): closed-loop
    # latency percentiles (lower is better), open-loop goodput, and
    # requests-per-ladder-launch coalescing ratio.  Latency floors are
    # wide: a CPU-container p99 carries the first-compile wall and
    # scheduler jitter.  BENCH_r06 is these keys' first artifact of
    # record (r01-r05 predate the serving section); until it lands the
    # trajectory shows them as named absences, not regressions
    ("serve_p50_ms", (), "lower", 0.30),
    ("serve_p99_ms", (), "lower", 0.40),
    ("serve_goodput_rps", (), "higher", 0.25),
    ("serve_coalesce_ratio", (), "higher", 0.20),
    # serving resilience (ISSUE 15, the chaos sub-run inside the
    # "serving" section): goodput retained under the seeded fault plan
    # vs the fault-free control (higher is better; exactness-gated to
    # None on any chaos-contract violation), and the chaos run's p99
    # (lower is better).  Floors are wide: both ride injected
    # sleep-scale faults on a contended CPU container
    ("serve_chaos_goodput_frac", (), "higher", 0.30),
    ("serve_chaos_p99_ms", (), "lower", 0.50),
    # request-lifecycle tail anatomy (ISSUE 19, inside the "serving"
    # section): the closed-loop p99 request's wall decomposed by the
    # reqtrace fold — fraction spent waiting to dispatch (lower is
    # better: queueing creep is the tail regression coalescing exists
    # to prevent) and fraction spent inside the device window (higher
    # is better: a healthy p99 is compute-bound, not queue-bound).
    # Floors are very wide: one request's split on a contended CPU
    # container swings with scheduler jitter and compile warmth
    ("serve_p99_queue_frac", (), "lower", 0.60),
    ("serve_p99_device_frac", (), "higher", 0.60),
    # recovery tier (ISSUE 13, bench section "resilience"): wall from an
    # injected degradation's first barrier to the drain taking effect
    # (lower is better), and windows for a kill-resume run to reconverge
    # its share split (lower is better).  Floors are wide: both ride
    # sleep-scale injections on a contended CPU container
    ("drain_recover_ms", (), "lower", 0.50),
    ("rejoin_converge_iters", (), "lower", 0.50),
    # cluster serving fabric (ISSUE 17, bench section "serving_fabric"):
    # goodput retained when a seeded mid-run member kill re-routes its
    # in-flight requests onto the surviving shards, vs the kill-free
    # control (higher is better; exactness-gated to None on any fabric
    # chaos-contract violation — a hung future or a torn result must
    # starve the key, never ship a number).  Floor is wide: the whole
    # run rides thread scheduling on a contended CPU container
    ("fabric_chaos_goodput_frac", (), "higher", 0.30),
    # persistent executable cache (ISSUE 18, bench section "cold_start"):
    # process-cold / cache-warm first-batch latency ratio for the n-body
    # ladder (higher is better; exactness-gated to None if the cache is
    # not bit-invisible).  Floor is wide: the numerator is one
    # subprocess's XLA compile wall on a contended CPU container
    ("cold_start_warm_speedup", (), "higher", 0.50),
    # heterogeneous lanes (ISSUE 20, bench section "hetero"): mixed
    # fast+slow fleet wall vs the best homogeneous subset at equal total
    # range (higher is better; exactness-gated to None unless all four
    # arms' result digests are bit-identical — a mixed fleet that
    # corrupts results must starve the key, never ship a speedup).
    # Floor is wide: on the CPU-only container the wall is the rate
    # model at each arm's converged split, but the splits themselves
    # ride measured benches under injected slow-link faults
    ("hetero_speedup_vs_best_homog", (), "higher", 0.30),
)

#: Trajectory-noise widening: tolerance = max(floor, NOISE_K * CV).
NOISE_K = 2.0

#: headline key -> bench section whose starvation reason explains a null
KEY_SECTION = {
    "flash_T8192_mfu_default": "flash_train",
    "flash_T8192_speedup_highest": "flash_train",
    "nbody_e2e_enqueue_gpairs": "nbody_e2e",
    "nbody_e2e_gpairs": "nbody_e2e",
    "dispatch_floor_collapse": "dispatch_floor",
    "overlap_balanced_raw": "overlap_balanced",
    "overlap_fraction_raw": "overlap_balanced",
    "dtype_cells": "dtype_matrix",
    "mandelbrot_mpix": "framework",
    "vs_tuned_loop": "tuned_loop",
    "repeat_mode_mpix": "repeat_mode",
    "serve_p50_ms": "serving",
    "serve_p99_ms": "serving",
    "serve_goodput_rps": "serving",
    "serve_coalesce_ratio": "serving",
    "serve_chaos_goodput_frac": "serving",
    "serve_chaos_p99_ms": "serving",
    "serve_p99_queue_frac": "serving",
    "serve_p99_device_frac": "serving",
    "drain_recover_ms": "resilience",
    "rejoin_converge_iters": "resilience",
    "fabric_chaos_goodput_frac": "serving_fabric",
    "cold_start_warm_speedup": "cold_start",
    "hetero_speedup_vs_best_homog": "hetero",
}


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


def extract_tail_object(text: str, key: str) -> dict | None:
    """Recover the LAST ``"key": {...}`` object from possibly-truncated
    JSON text by balanced-brace scanning (string-aware).  Returns None
    when the key or a complete object isn't there."""
    pat = re.compile(r'"%s"\s*:\s*\{' % re.escape(key))
    last = None
    for m in pat.finditer(text):
        last = m
    if last is None:
        return None
    i = last.end() - 1  # the opening brace
    depth = 0
    in_str = False
    esc = False
    for j in range(i, len(text)):
        ch = text[j]
        if in_str:
            if esc:
                esc = False
            elif ch == "\\":
                esc = True
            elif ch == '"':
                in_str = False
            continue
        if ch == '"':
            in_str = True
        elif ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                try:
                    return json.loads(text[i : j + 1])
                except json.JSONDecodeError:
                    return None
    return None


def load_headline(path: str) -> dict:
    """Load one artifact (driver wrapper, raw bench line, or parsed
    dict) → ``{"headline": ..., "errors": ..., "null_sections": ...,
    "sections": raw-or-None, "path": ...}``.  Missing pieces come back
    None, never raise.  ``null_sections`` is bench.py's compact
    section → ``{"null_reason", "budget_spent_s"}`` map, emitted just
    before the headline precisely so it survives the driver's
    2000-char tail truncation."""
    out = {"path": path, "headline": None, "errors": None,
           "null_sections": None, "sections": None}
    try:
        with open(path) as f:
            text = f.read()
    except (OSError, UnicodeDecodeError) as e:
        # UnicodeDecodeError: a binary/garbled artifact must degrade to
        # "no headline" like every other unparseable shape — the CLI
        # turns that into its one-line verdict, never a traceback
        out["errors"] = {"_load": f"{type(e).__name__}: {e}"}
        return out
    doc = None
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        pass
    if isinstance(doc, dict) and "headline" in doc:
        # a raw bench.py result line
        out["headline"] = doc.get("headline")
        out["errors"] = doc.get("errors")
        out["null_sections"] = doc.get("null_sections")
        out["sections"] = doc
        return out
    if isinstance(doc, dict) and "tail" in doc:
        parsed = doc.get("parsed")
        if isinstance(parsed, dict) and parsed.get("headline") is not None:
            out["headline"] = parsed.get("headline")
            out["errors"] = parsed.get("errors")
            out["null_sections"] = parsed.get("null_sections")
            out["sections"] = parsed
            return out
        text = doc.get("tail") or ""
    # truncated tail (or unknown shape): recover the trailing objects.
    # `out` is the linter's PARSED VIEW of an artifact, not an artifact
    # itself — key order here carries no tail-survival contract
    out["headline"] = extract_tail_object(text, "headline")
    # ckcheck: ok parsed view, not an artifact — headline-last n/a
    out["errors"] = extract_tail_object(text, "errors")
    # ckcheck: ok parsed view, not an artifact — headline-last n/a
    out["null_sections"] = extract_tail_object(text, "null_sections")
    return out


def _get(headline: dict | None, key: str, aliases=()) -> float | None:
    if not isinstance(headline, dict):
        return None
    for k in (key, *aliases):
        v = headline.get(k)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            return float(v)
    return None


def _null_reason(candidate: dict, key: str) -> str:
    """Best starvation/failure reason the candidate artifact offers for
    a missing watched key: the tail-surviving ``null_sections`` map
    first, then the section's own annotated record, then ``errors``."""
    section = KEY_SECTION.get(key)
    if not section:
        return "no reason recorded in artifact"
    for source in (candidate.get("null_sections"), candidate.get("sections")):
        if isinstance(source, dict):
            rec = source.get(section)
            if isinstance(rec, dict) and rec.get("null_reason"):
                spent = rec.get("budget_spent_s")
                return f"{rec['null_reason']} (budget_spent_s={spent})"
    errors = candidate.get("errors")
    if isinstance(errors, dict) and section in errors:
        return str(errors[section])
    return "no reason recorded in artifact"


def _trajectory_cv(history: list[dict], key: str, aliases=()) -> float | None:
    vals = [v for v in (_get(h, key, aliases) for h in history)
            if v is not None]
    if len(vals) < 3:
        return None
    mean = sum(vals) / len(vals)
    if mean == 0:
        return None
    var = sum((v - mean) ** 2 for v in vals) / (len(vals) - 1)
    return (var ** 0.5) / abs(mean)


def diff_headlines(
    baseline: dict,
    candidate: dict,
    history: list[dict] | None = None,
    watched=WATCHED_KEYS,
) -> dict:
    """The sentinel's core: compare two loaded artifacts
    (:func:`load_headline` output) on the watched headline keys.

    Returns ``{"ok", "exit_code", "findings": [...], "checked": N}``
    with one finding per violated key — kind "regression" (beyond
    noise-aware tolerance) or "starved" (baseline had it, candidate
    nulls it, reason attached)."""
    findings: list[dict] = []
    checked = 0
    base_h, cand_h = baseline.get("headline"), candidate.get("headline")
    if not isinstance(cand_h, dict):
        return {
            "ok": False, "exit_code": 3, "checked": 0,
            "findings": [{
                "kind": "starved", "key": "headline",
                "reason": "candidate artifact carries no headline block "
                          "at all (bench died before the tail-survival "
                          "block printed)",
            }],
        }
    for key, aliases, direction, floor in watched:
        base_v = _get(base_h, key, aliases)
        if base_v is None:
            continue  # nothing to regress against
        checked += 1
        cand_v = _get(cand_h, key, aliases)
        if cand_v is None:
            findings.append({
                "kind": "starved", "key": key, "baseline": base_v,
                "reason": _null_reason(candidate, key),
            })
            continue
        tol = floor
        cv = _trajectory_cv(
            [h.get("headline") or {} for h in (history or [])],
            key, aliases,
        )
        if cv is not None:
            tol = max(floor, NOISE_K * cv)
        if direction == "higher":
            drop = (base_v - cand_v) / abs(base_v) if base_v else 0.0
        else:
            drop = (cand_v - base_v) / abs(base_v) if base_v else 0.0
        if drop > tol:
            findings.append({
                "kind": "regression", "key": key,
                "baseline": base_v, "candidate": cand_v,
                "drop_frac": round(drop, 4), "tolerance": round(tol, 4),
            })
    # decision-provenance drift: replay_ok is bench.py's in-process
    # replay-verify verdict over the run's recorded controller
    # decisions.  False = the decision code did not reproduce its own
    # log — a hard failure of the same severity class as a starved key
    # (True and absent — pre-provenance artifacts — both pass).
    if cand_h.get("replay_ok") is False:
        dec = None
        sections = candidate.get("sections")
        if isinstance(sections, dict):
            dec = sections.get("decisions")
        first = (dec or {}).get("replay", {}).get("first_divergence") \
            if isinstance(dec, dict) else None
        findings.append({
            "kind": "replay-drift", "key": "replay_ok",
            "reason": (
                "the artifact's decision log did not replay "
                "bit-identically (behavior drift in a controller); "
                + (f"first divergence: {first}" if first else
                   "run `python -m tools.ckreplay verify` on the run's "
                   "CK_DECISION_LOG spill for the divergent seq")),
        })
    # model-check drift (ISSUE 14): model_ok is bench.py's in-process
    # bounded exhaustive exploration of the controller machines
    # against their declared MODEL_INVARIANTS.  False = a controller
    # violates a machine-checked temporal invariant (flaps, starves,
    # leaks share, diverges) — the same hard-failure class as replay
    # drift (True and absent — pre-model artifacts — both pass).
    if cand_h.get("model_ok") is False:
        findings.append({
            "kind": "model-drift", "key": "model_ok",
            "reason": (
                "the artifact's bounded model check refuted a declared "
                "controller invariant; run `python -m tools.ckmodel` "
                "for the violation and its minimal counterexample "
                "trace (--explain <fp>, --save-trace)"),
        })
    hard = any(f["kind"] in ("starved", "replay-drift", "model-drift")
               for f in findings)
    regressed = any(f["kind"] == "regression" for f in findings)
    code = 3 if hard else (2 if regressed else 0)
    return {
        "ok": code == 0, "exit_code": code, "checked": checked,
        "findings": findings,
    }


def _round_key(path: str):
    """Numeric round ordering: lexicographic basenames misorder r99 vs
    r100 (and unpadded names), which would gate a fresh artifact
    against the wrong round."""
    m = re.search(r"BENCH_r(\d+)", os.path.basename(path))
    return (int(m.group(1)) if m else -1, os.path.basename(path))


def _artifact_paths(root: str) -> list[str]:
    return sorted(glob.glob(os.path.join(root, "BENCH_r*.json")),
                  key=_round_key)


def bench_epilogue(result: dict, repo_root: str) -> dict | None:
    """In-process sentinel pass for a fresh ``bench.py`` result: diff
    its headline against the newest on-disk artifact (the previous
    round), with the whole trajectory as the noise model.  Returns the
    verdict dict (embedded in the result) or None when there is no
    prior artifact.  Never raises — the bench's one-JSON-line contract
    outranks the sentinel."""
    try:
        paths = _artifact_paths(repo_root)
        if not paths:
            return None
        history = [load_headline(p) for p in paths]
        # newest artifact WITH a recoverable headline: a truncated/
        # crashed previous round must not silently disable the sentinel
        # (diff_headlines only hard-fails a headline-less CANDIDATE; a
        # headline-less baseline would check 0 keys and report ok:true)
        baseline = next(
            (h for h in reversed(history)
             if isinstance(h.get("headline"), dict)), None)
        if baseline is None:
            return {
                "ok": None,
                "error": "no on-disk artifact carries a recoverable "
                         "headline — nothing to gate against",
            }
        candidate = {
            "path": "<this run>", "headline": result.get("headline"),
            "errors": result.get("errors"),
            "null_sections": result.get("null_sections"),
            "sections": result,
        }
        verdict = diff_headlines(baseline, candidate, history=history)
        verdict["against"] = os.path.basename(baseline["path"])
        return verdict
    except Exception as e:  # noqa: BLE001 - resilience boundary
        return {"ok": None, "error": f"{type(e).__name__}: {e}"[:300]}


#: history_table cell sentinel: the ROUND is missing from the on-disk
#: trajectory (vs "null" — the round ran but starved the key).
_GAP = object()


def no_trajectory_message(root: str) -> str | None:
    """The one-line actionable verdict when the trajectory cannot gate
    anything: no artifacts at all, or none that parses to a headline.
    Returns None when at least one artifact carries a headline."""
    paths = _artifact_paths(root)
    if not paths:
        return (f"regress: no BENCH_r*.json artifacts under {root} — "
                "run `python bench.py | tee BENCH_r<N>.json` to start a "
                "trajectory")
    if all(load_headline(p).get("headline") is None for p in paths):
        return (f"regress: none of the {len(paths)} BENCH_r*.json "
                f"artifact(s) under {root} parses to a headline block — "
                "re-run `python bench.py` (artifacts predating the "
                "headline contract, or truncated/corrupt, cannot gate)")
    return None


def history_table(root: str, watched=WATCHED_KEYS) -> str:
    """Compact per-key trajectory table over the on-disk ``BENCH_r*``
    artifacts: one row per watched key, one column per round, plus the
    trajectory CV and the effective (noise-widened) tolerance — bench
    regressions eyeballed without opening five JSON files.  Rounds
    MISSING from the trajectory (r03 absent between r02 and r04) render
    as ``-`` gap columns, distinct from ``null`` (the round ran but the
    key starved)."""
    paths = _artifact_paths(root)
    empty = no_trajectory_message(root)
    if empty is not None:
        return f"({empty[len('regress: '):]})" if paths else \
            f"(no BENCH_r*.json artifacts under {root})"
    history = [load_headline(p) for p in paths]
    rounds = []
    nums = []
    for p in paths:
        m = re.search(r"BENCH_r(\d+)", os.path.basename(p))
        rounds.append(f"r{m.group(1)}" if m else os.path.basename(p)[:8])
        nums.append(int(m.group(1)) if m else None)
    heads = [h.get("headline") or {} for h in history]
    # splice gap columns for rounds absent between the first and last
    # present round (numeric ordering — _round_key sorted the paths)
    by_num: dict[int, tuple[str, object]] = {}
    extras: list[tuple[str, object]] = []
    for r, h, num in zip(rounds, heads, nums):
        if num is None:
            extras.append((r, h))
        else:
            by_num.setdefault(num, (r, h))
    cols: list[tuple[str, object]] = []
    if by_num:
        for n in range(min(by_num), max(by_num) + 1):
            cols.append(by_num.get(n, (f"r{n:02d}", _GAP)))
    cols.extend(extras)
    col_names = [c[0] for c in cols]
    key_w = max(len(k) for k, *_ in watched)
    col_w = max(8, max(len(r) for r in col_names) + 1)
    lines = [
        f"{'key':<{key_w}} "
        + "".join(f"{r:>{col_w}}" for r in col_names)
        + f" {'CV':>7} {'tol':>7}"
    ]
    for key, aliases, _direction, floor in watched:
        vals = [
            _GAP if h is _GAP else _get(h, key, aliases) for _r, h in cols
        ]
        if all(v is None or v is _GAP for v in vals):
            continue

        def cell(v):
            if v is _GAP:
                return f"{'-':>{col_w}}"
            if v is None:
                return f"{'null':>{col_w}}"
            return f"{v:>{col_w}.4g}"

        cv = _trajectory_cv(heads, key, aliases)
        tol = max(floor, NOISE_K * cv) if cv is not None else floor
        cv_cell = f"{cv:>7.3f}" if cv is not None else f"{'-':>7}"
        lines.append(
            f"{key:<{key_w}} " + "".join(cell(v) for v in vals)
            + f" {cv_cell} {tol:>7.3f}"
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--against", default=None,
                    help="baseline artifact (e.g. BENCH_r05.json)")
    ap.add_argument("--history", action="store_true",
                    help="print the per-key trajectory table (value per "
                         "round + CV + effective tolerance) and exit")
    ap.add_argument("--candidate", default=None,
                    help="candidate artifact or raw bench output "
                         "(default: newest BENCH_r*.json != --against)")
    ap.add_argument("--json", action="store_true",
                    help="print the verdict as JSON")
    ap.add_argument("--root", default=None,
                    help="directory holding the BENCH_r*.json trajectory "
                         "(default: the repo root)")
    args = ap.parse_args(argv)

    root = args.root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    if args.history:
        print(history_table(root))
        return 0
    if not args.against:
        ap.error("--against is required (or use --history)")
    # an empty/unparseable trajectory is a one-line actionable verdict,
    # never a traceback and never a vacuous "0 keys checked" pass
    if args.candidate is None:
        msg = no_trajectory_message(root)
        if msg is not None:
            print(msg, file=sys.stderr)
            return 1
    baseline = load_headline(args.against)
    if baseline["headline"] is None:
        print(f"regress: no headline recoverable from baseline "
              f"{args.against} — pick a baseline artifact that carries "
              "one (see --history), or re-run `python bench.py`",
              file=sys.stderr)
        return 1
    cand_path = args.candidate
    if cand_path is None:
        # only artifacts NEWER than the baseline qualify: picking an
        # older round would diff time-backwards (improvements would
        # read as regressions and vice versa).  A baseline outside the
        # BENCH_r<N> naming has no round to compare against — require
        # an explicit candidate rather than letting the -1 fallback key
        # mark every artifact "newer"
        if not re.search(r"BENCH_r(\d+)", os.path.basename(args.against)):
            print(
                f"regress: baseline {args.against} does not follow "
                "BENCH_r<N> naming — pass --candidate explicitly",
                file=sys.stderr,
            )
            return 1
        newer = [
            p for p in _artifact_paths(root)
            if _round_key(p) > _round_key(args.against)
        ]
        if not newer:
            print(
                f"regress: no artifact newer than {args.against} — pass "
                "--candidate explicitly", file=sys.stderr,
            )
            return 1
        cand_path = newer[-1]
    candidate = load_headline(cand_path)
    # the candidate must NOT feed the noise model: a regressed artifact
    # would inflate the trajectory CV and widen its own tolerance
    # (verified failure mode: a 30% drop masking itself)
    history = [
        load_headline(p) for p in _artifact_paths(root)
        if os.path.abspath(p) != os.path.abspath(cand_path)
    ]
    verdict = diff_headlines(baseline, candidate, history=history)
    verdict["against"] = args.against
    verdict["candidate"] = cand_path
    if args.json:
        print(json.dumps(_json_safe(verdict), indent=2, allow_nan=False))
    else:
        status = "OK" if verdict["ok"] else "FAIL"
        print(f"regress {status}: {verdict['checked']} keys checked vs "
              f"{os.path.basename(args.against)}")
        for f in verdict["findings"]:
            if f["kind"] == "starved":
                print(f"  STARVED {f['key']}: baseline had "
                      f"{f.get('baseline')}, candidate is null — "
                      f"{f['reason']}")
            elif f["kind"] == "replay-drift":
                print(f"  REPLAY-DRIFT {f['key']}: {f['reason']}")
            elif f["kind"] == "model-drift":
                print(f"  MODEL-DRIFT {f['key']}: {f['reason']}")
            else:
                print(f"  REGRESSION {f['key']}: {f['baseline']} -> "
                      f"{f['candidate']} (drop {f['drop_frac']:.1%} > "
                      f"tol {f['tolerance']:.1%})")
    return verdict["exit_code"]


if __name__ == "__main__":
    sys.exit(main())
