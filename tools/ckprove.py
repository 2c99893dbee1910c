"""``python -m tools.ckprove`` — kernel partition-safety verifier CLI.

The repo-corpus face of ``cekirdekler_tpu/analysis/`` (the abstract
interpreter behind the ``CK_KERNEL_VERIFY`` runtime gate): scans the
repo's Python files for embedded kernel-language sources (string
literals containing ``__kernel``), summarizes every kernel's array
accesses, and ratchets the **flag-independent split-safety errors**
(``scatter-write`` / ``off-partition-write`` — a store the balancer's
re-partitioning would silently drop on any >1-lane split) against
``tools/ckprove_baseline.json``.  Flag-dependent verdicts (halo under
``partial_read``, read-before-write under ``write_only``) need the
call site's :class:`TransferFlags` and are enforced at runtime by
``Cores.compute``/serve admission; the CLI's ``--json`` report carries
the per-array access *facts* (confined / halo / gather / rbw) so flag
reviews read them without running anything.

Mirrors the ckcheck lifecycle exactly: exit 0 = no findings beyond
the baseline AND no stale entries; ``--update-baseline`` refuses
growth without ``--allow-grow``; ``// ckprove: ok <why>`` on the
offending kernel-source line suppresses.  Import discipline: the
analyzer rides only ``kernel/lang.py`` + ``analysis/`` — when the full
package (and its jax import) is unavailable, a stub package loader
brings in exactly those modules, so the CLI runs on rigs where the
runtime is broken (the ckcheck/lint_obs contract).

Usage::

    python -m tools.ckprove                  # the CI gate
    python -m tools.ckprove --explain <fp>   # one finding, full detail
    python -m tools.ckprove --update-baseline [--allow-grow]
    python -m tools.ckprove --json           # facts + findings dump
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import re
import sys

#: A string literal is a kernel SOURCE (not a docstring mentioning the
#: keyword, not the lexer's keyword table) iff it contains an actual
#: kernel definition head.
_KERNEL_DEF_RE = re.compile(r"(?:__kernel|kernel)\s+void\s+\w+\s*\(")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "ckprove_baseline.json")

#: What the corpus scan covers.  tests/ is deliberately EXCLUDED: the
#: differential-oracle corpus there plants unsafe kernels on purpose.
SCAN_ROOTS = ("cekirdekler_tpu", "examples")

if REPO not in sys.path:  # direct-script invocation
    sys.path.insert(0, REPO)

from tools.ckcheck.baseline import (  # noqa: E402
    load_baseline,
    load_baseline_doc,
    provenance_note,
    ratchet,
    save_baseline,
)


def _load_analysis():
    """``(lang, analysis)`` — the parser and the verifier.

    Fast path: the installed package (jax present).  Fallback: stub
    parent packages so ``kernel/lang.py`` and ``analysis/`` load
    WITHOUT executing ``cekirdekler_tpu/__init__.py`` (which imports
    jax via hardware/metrics/obs) — the run-anywhere discipline.
    """
    try:
        from cekirdekler_tpu import analysis
        from cekirdekler_tpu.kernel import lang

        return lang, analysis
    except Exception:  # noqa: BLE001 - jax/runtime broken: stub-load
        import importlib
        import types

        pkgroot = os.path.join(REPO, "cekirdekler_tpu")
        for name, path in (
            ("cekirdekler_tpu", pkgroot),
            ("cekirdekler_tpu.kernel", os.path.join(pkgroot, "kernel")),
        ):
            if name not in sys.modules:
                mod = types.ModuleType(name)
                mod.__path__ = [path]  # type: ignore[attr-defined]
                sys.modules[name] = mod
        lang = importlib.import_module("cekirdekler_tpu.kernel.lang")
        analysis = importlib.import_module("cekirdekler_tpu.analysis")
        return lang, analysis


_JSONSAFE = None


def _json_safe(o):
    """Delegates to tools/_jsonsafe.py (loaded by file path — the
    shared standalone-tool sanitizer, so future fixes reach every
    tool's --json output at once)."""
    global _JSONSAFE
    if _JSONSAFE is None:
        import importlib.util

        p = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "_jsonsafe.py")
        spec = importlib.util.spec_from_file_location(
            "ck_tools_jsonsafe", p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _JSONSAFE = mod.json_safe
    return _JSONSAFE(o)


def iter_kernel_sources(root: str | None = None):
    """Yield ``(relpath, lineno, source)`` for every string literal
    containing ``__kernel`` in the scan roots — pure ``ast`` over the
    Python files, no imports of the scanned code.  f-strings cannot be
    evaluated statically and are skipped (none of the repo's benchable
    kernels live in one; the generated dtype-matrix kernel is runtime-
    verified instead)."""
    root = root or REPO
    paths = []
    for entry in SCAN_ROOTS:
        full = os.path.join(root, entry)
        if os.path.isfile(full):
            paths.append(full)
        elif os.path.isdir(full):
            for dirpath, _dirnames, filenames in os.walk(full):
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        paths.append(os.path.join(dirpath, fn))
    for path in sorted(paths):
        rel = os.path.relpath(path, root)
        try:
            with open(path) as f:
                tree = ast.parse(f.read())
        except (OSError, SyntaxError):
            continue
        # docstrings mentioning the language (lang.py's own docs) are
        # not kernel sources: mark every body-leading string Expr
        docstrings: set = set()
        for node in ast.walk(tree):
            if isinstance(node, (ast.Module, ast.FunctionDef,
                                 ast.AsyncFunctionDef, ast.ClassDef)):
                body = getattr(node, "body", [])
                if body and isinstance(body[0], ast.Expr) and \
                        isinstance(body[0].value, ast.Constant):
                    docstrings.add(id(body[0].value))
            elif isinstance(node, ast.JoinedStr):
                # f-string pieces: not statically evaluable — the
                # dtype-matrix generator's kernels are runtime-verified
                # by the Cores gate instead
                for part in ast.walk(node):
                    docstrings.add(id(part))
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str) and \
                    id(node) not in docstrings and \
                    _KERNEL_DEF_RE.search(node.value):
                yield rel, node.lineno, node.value


def analyze_corpus(root: str | None = None):
    """``(findings, facts)`` over the repo corpus.

    ``findings`` are the ratcheted split-safety errors (plus
    ``unparsed`` for a kernel string the front end rejects — a stale
    snippet is debt too).  ``facts`` is one row per kernel with its
    per-array access classes, for ``--json`` consumers."""
    lang, analysis = _load_analysis()
    findings: list = []
    facts: list = []
    seen_sources: set = set()
    for rel, lineno, source in iter_kernel_sources(root):
        key = (rel, source)
        if key in seen_sources:
            continue
        seen_sources.add(key)
        try:
            kdefs = lang.parse_kernels(source)
        except Exception as e:  # noqa: BLE001 - unparseable = finding
            findings.append(analysis.Finding(
                kind="unparsed", severity="error", where=rel,
                kernel=f"@{lineno}", param="*", line=lineno,
                message=f"kernel string at {rel}:{lineno} does not "
                        f"parse: {type(e).__name__}: {e}"))
            continue
        for kdef in kdefs:
            try:
                summary = analysis.summarize_kernel(kdef)
            except Exception as e:  # noqa: BLE001 - analysis bail-out
                facts.append({"path": rel, "kernel": kdef.name,
                              "error": f"{type(e).__name__}: {e}"})
                continue
            findings.extend(
                analysis.structural_findings(summary, where=rel))
            row = {"path": rel, "kernel": kdef.name, "arrays": {}}
            for pname in summary.array_params:
                reads = sorted({
                    analysis.classify(a.av, 1)[0]
                    for a in summary.reads.get(pname, ())})
                writes = sorted({
                    analysis.classify(a.av, 1)[0]
                    for a in summary.writes.get(pname, ())})
                row["arrays"][pname] = {
                    "reads": reads,
                    "writes": writes,
                    # the proved reach of each halo read: offsets beside
                    # the item's own element, value parameters by name
                    # (``-width``: one row up); what a launch's exchange
                    # keeps current (docs/KERNEL_LANGUAGE.md)
                    "reach": sorted({
                        str(analysis.reach_of(a.av))
                        for a in summary.reads.get(pname, ())
                        if analysis.classify(a.av, 1)[0] == "halo"}),
                    "partial_eligible": bool(reads) and
                    reads == ["confined"],
                    "read_before_write": summary.rbw.get(pname),
                }
            facts.append(row)
    findings.sort(key=lambda f: (f.where, f.kernel, f.line))
    return findings, facts


_DOC_PATH = os.path.join(REPO, "docs", "STATIC_ANALYSIS.md")


def doc_verdict_kinds(doc_text: str | None = None) -> set:
    """Verdict kinds listed in docs/STATIC_ANALYSIS.md's "verdict
    vocabulary" table — the doc side of the two-way drift check
    (tests/test_ckprove.py pins it against VERDICT_KINDS)."""
    if doc_text is None:
        with open(_DOC_PATH) as f:
            doc_text = f.read()
    m = re.search(
        r"### The verdict vocabulary(.*?)(?:\n### |\n## |\Z)",
        doc_text, re.S)
    if not m:
        return set()
    return set(re.findall(r"^\|\s*`([a-z][a-z-]*)`", m.group(1), re.M))


RULE_DOCS = {
    "off-partition-write": (
        "The kernel stores to an index that provably leaves the "
        "calling work item's partition (a halo offset, a stride other "
        "than elements_per_work_item, or a uniform index every item "
        "hits).  Each lane writes back only its own slice, so the "
        "off-partition store is silently dropped — results differ "
        "between split and unsplit runs.  Fix: confine stores to "
        "epw*gid + [0, epw), or restructure into a separate kernel "
        "whose range covers the written region."),
    "scatter-write": (
        "The kernel stores through a gathered/indirect index (data-"
        "dependent, modular, or otherwise non-affine in "
        "get_global_id(0)).  Nothing proves the store lands inside the "
        "caller's partition, and the balancer is free to re-partition "
        "at any call.  Fix: make the store gid-affine, or suppress the "
        "line with `// ckprove: ok <why>` when out-of-partition "
        "stores are provably impossible for your data."),
    "unparsed": (
        "A string containing `__kernel` does not parse under the "
        "kernel-language front end — either a stale snippet or a "
        "construct outside the supported surface.  Fix or delete it; "
        "dead kernel strings rot into documentation lies."),
    "verdict-kinds": "see docs/STATIC_ANALYSIS.md 'Kernel partition-safety'",
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.ckprove",
        description="kernel partition-safety & flag-soundness verifier "
                    "(docs/STATIC_ANALYSIS.md)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from current findings "
                         "(refuses NEW findings without --allow-grow)")
    ap.add_argument("--allow-grow", action="store_true",
                    help="permit --update-baseline to add findings")
    ap.add_argument("--explain", metavar="FINGERPRINT",
                    help="print one finding with its rule documentation")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable findings + per-kernel access "
                         "facts (exit code semantics unchanged)")
    ap.add_argument("--root", default=None,
                    help="repo root to scan (default: this repo)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline file (default: tools/"
                         "ckprove_baseline.json)")
    args = ap.parse_args(argv)

    if args.explain == "provenance":
        # derived solely from the baseline file — never pay the scan
        print(provenance_note(load_baseline_doc(args.baseline)))
        return 0

    findings, facts = analyze_corpus(args.root)
    baseline = load_baseline(args.baseline)
    new, grand, stale = ratchet(findings, baseline)

    if args.explain:
        for f in findings:
            if f.fingerprint.startswith(args.explain):
                print(f.render())
                print()
                print(RULE_DOCS.get(f.kind, "(no rule documentation)"))
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
            print(f"ckprove: REFUSING to grow the baseline by "
                  f"{len(new)} new finding(s) (pass --allow-grow to "
                  "grandfather deliberately):")
            for f in new:
                print("  " + f.render())
            return 1
        save_baseline(args.baseline, findings, tool="ckprove")
        print(f"ckprove: baseline rewritten: {len(findings)} finding(s) "
              f"({len(new)} added, {len(stale)} removed)")
        return 0

    if args.json:
        print(json.dumps(_json_safe({
            "new": [f.to_row() for f in new],
            "grandfathered": [f.to_row() for f in grand],
            "stale_baseline": stale,
            "kernels": facts,
        }), indent=1, sort_keys=True, allow_nan=False))
        return 0 if not new and not stale else 1

    ok = True
    if new:
        ok = False
        print(f"ckprove: {len(new)} NEW finding(s) (not in baseline):")
        for f in new:
            print("  " + f.render())
        print("  (fix them, suppress `// ckprove: ok <why>` on the "
              "kernel-source line, or --update-baseline --allow-grow "
              "to grandfather)")
    if stale:
        ok = False
        print(f"ckprove: {len(stale)} STALE baseline entr"
              f"{'y' if len(stale) == 1 else 'ies'} (finding fixed but "
              "baseline not shrunk — run --update-baseline):")
        for row in stale:
            print(f"  [{row['fingerprint']}] {row.get('path')}:"
                  f"{row.get('line')} {row.get('message', '')[:80]}")
        print("  (" + provenance_note(
            load_baseline_doc(args.baseline)) + ")")
    if ok:
        n_kernels = sum(1 for r in facts if "arrays" in r)
        print(f"ckprove: clean — {n_kernels} kernel(s) verified, "
              f"{len(findings)} grandfathered finding(s) remain in the "
              "baseline (ratchet: this number only goes down)")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
