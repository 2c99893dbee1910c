"""ckprove — kernel partition-safety & flag-soundness verifier: the
differential-oracle acceptance suite.

Layers:

1. **Differential oracle agreement** — every corpus verdict
   (tests/kernel_corpus.py) is checked against ground truth: each of
   the ≥8 unsafe kernels is caught with its named finding + source
   line AND provably corrupts under a ≥2-lane split (or lies about its
   flags) per the lane simulator; every safe kernel is clean AND
   bit-identical split vs unsplit.  Zero false negatives on the
   corpus, false positives only as advisories.
2. **Runtime gates** — ``CK_KERNEL_VERIFY=strict`` makes
   ``Cores.compute`` raise :class:`KernelVerifyError` with the named
   finding, and serve admission reject with the named
   ``kernel-unsafe`` reason whose decision record replays
   bit-identically through the ``ckreplay verify`` engine.  A real
   2-chip vs 1-chip run anchors the simulator to the actual machine.
3. **CLI lifecycle** — ``python -m tools.ckprove`` exits 0 on HEAD
   against the checked-in baseline; new findings fail;
   ``--update-baseline`` refuses growth without ``--allow-grow``;
   ``// ckprove: ok`` suppresses; the docs' verdict table matches
   :data:`VERDICT_KINDS` (the lint_obs two-way discipline).
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cekirdekler_tpu import ClArray, analysis  # noqa: E402
from cekirdekler_tpu.core.cruncher import NumberCruncher  # noqa: E402
from cekirdekler_tpu.errors import KernelVerifyError  # noqa: E402
from cekirdekler_tpu.hardware import platforms  # noqa: E402
from tests.kernel_corpus import (  # noqa: E402
    CORPUS,
    SAFE,
    UNSAFE,
    build,
    ground_truth_unsafe,
    run_lanes,
    verdict_for,
)

import tools.ckprove as ckprove  # noqa: E402


@pytest.fixture(scope="module")
def devs():
    return platforms().cpus()


# ---------------------------------------------------------------------------
# 1. the differential oracle
# ---------------------------------------------------------------------------

def test_corpus_shape():
    """The acceptance floor: ≥20 kernels, ≥8 deliberately unsafe."""
    assert len(CORPUS) >= 20
    assert len(UNSAFE) >= 8


@pytest.mark.parametrize("entry", CORPUS, ids=lambda e: e.name)
def test_verdict_matches_differential_oracle(entry):
    """Per kernel: the verifier's error kinds are exactly the declared
    expectation, each finding carries a real source line, and the
    split-vs-unsplit oracle confirms the verdict bit-exactly."""
    v = verdict_for(entry)
    kinds = {f.kind for f in v.errors}
    assert set(entry.expect) <= kinds, (
        f"{entry.name}: expected {entry.expect}, verifier found {kinds}")
    assert bool(kinds) == bool(entry.expect), (
        f"{entry.name}: unexpected error kinds {kinds - set(entry.expect)}")
    for f in v.errors:
        assert f.line > 0, f"{entry.name}: finding without a source line"
        assert f.kernel, f
    assert ground_truth_unsafe(entry) == bool(entry.expect), (
        f"{entry.name}: differential oracle disagrees with the verdict")


def test_zero_false_negatives_across_corpus():
    """THE contract: no kernel the oracle proves unsafe escapes with a
    clean verdict — at 2 AND 3 lanes."""
    for entry in CORPUS:
        for lanes in (2, 3):
            if ground_truth_unsafe(entry, lanes=lanes):
                assert not verdict_for(entry).ok, (
                    f"FALSE NEGATIVE: {entry.name} corrupts at "
                    f"{lanes} lanes but the verifier passed it")


def test_false_positives_only_as_advisories():
    """A clean-by-oracle kernel may collect advisories (partial-safe,
    unread-upload) but never an error-severity finding."""
    for entry in SAFE:
        v = verdict_for(entry)
        assert v.ok, (
            f"FALSE POSITIVE: {entry.name} is oracle-clean but got "
            f"errors {[f.kind for f in v.errors]}")


def test_suppression_comment_silences_finding():
    from tests.kernel_corpus import CorpusKernel

    entry = CorpusKernel(
        "halo_suppressed", """
__kernel void sh(__global float* x, __global float* y) {
    int i = get_global_id(0);
    y[i] = x[i+1];  // ckprove: ok halo is caller-padded in this app
}""", (dict(partial_read=True, read_only=True),
       dict(partial_read=True, write_only=True)))
    assert verdict_for(entry).ok


def test_partial_safe_advisory_names_free_h2d():
    """An over-broad full read on a gid-confined access surfaces as
    the partial-safe advisory (the satellite-fix detector)."""
    from tests.kernel_corpus import CorpusKernel

    entry = CorpusKernel(
        "overbroad", """
__kernel void ob(__global float* x, __global float* y) {
    int i = get_global_id(0);
    y[i] = x[i] * 2.0f;
}""", (dict(read_only=True), dict(partial_read=True, write_only=True)))
    v = verdict_for(entry)
    assert v.ok
    assert any(f.kind == "partial-safe" and f.param == "x"
               for f in v.advisories)


# ---------------------------------------------------------------------------
# 2. runtime gates
# ---------------------------------------------------------------------------

_HALO_SRC = """
__kernel void sh(__global float* x, __global float* y) {
    int i = get_global_id(0);
    y[i] = x[i+1] + x[i];
}
"""

_SAXPY_SRC = """
__kernel void saxpy(__global float* x, __global float* y, float a) {
    int i = get_global_id(0);
    y[i] = a * x[i] + y[i];
}
"""


def _halo_args(n=256):
    x = ClArray(np.arange(n, dtype=np.float32), name="vx",
                partial_read=True, read_only=True)
    y = ClArray(n, np.float32, name="vy", partial_read=True)
    return x, y


# Since PR 32 the PLAIN path keeps a proved reach current (the slice a
# ``partial_read`` uploads is widened by it: tests/test_halo_exchange.py),
# so the halo kernel is refused only where nothing does: a pipelined
# compute uploads blob by blob.
_PIPELINED = dict(pipeline=True, pipeline_blobs=2)


def test_strict_gate_raises_named_finding(devs, monkeypatch):
    monkeypatch.setenv("CK_KERNEL_VERIFY", "strict")
    cr = NumberCruncher(devs.subset(2), _HALO_SRC)
    try:
        x, y = _halo_args()
        with pytest.raises(KernelVerifyError) as ei:
            x.next_param(y).compute(cr, 70, "sh", 256, 32, **_PIPELINED)
        assert ei.value.finding.kind == "partial-read-halo"
        assert ei.value.finding.line == 4
        assert "partial-read-halo" in str(ei.value)
    finally:
        cr.dispose()


def test_advisory_default_computes_and_flight_records(devs, monkeypatch):
    """Advisory (default) mode: the unsafe launch still runs (legacy
    behavior preserved) but the flight ring records the named finding
    ONCE per launch shape."""
    from cekirdekler_tpu.obs.flight import FLIGHT

    monkeypatch.delenv("CK_KERNEL_VERIFY", raising=False)
    cr = NumberCruncher(devs.subset(2), _HALO_SRC)
    try:
        x, y = _halo_args()
        for _ in range(3):
            x.next_param(y).compute(cr, 71, "sh", 256, 32, **_PIPELINED)
        evs = [e for e in FLIGHT.snapshot()
               if e.kind == "kernel-verify"
               and e.fields.get("kernels") == "sh"]
        assert len(evs) == 1, evs
        assert evs[0].fields["finding"] == "partial-read-halo"
    finally:
        cr.dispose()


def test_verify_off_skips_gate(devs, monkeypatch):
    monkeypatch.setenv("CK_KERNEL_VERIFY", "off")
    cr = NumberCruncher(devs.subset(2), _HALO_SRC)
    try:
        x, y = _halo_args()
        x.next_param(y).compute(cr, 72, "sh", 256, 32)
        assert not cr.cores.program._verdict_cache
    finally:
        cr.dispose()


def test_real_split_anchors_the_simulator(devs):
    """The lane simulator's verdicts hold on the REAL machine: the
    halo-under-partial kernel diverges 2-chip vs 1-chip bit-exactly
    where the simulator says it does, and the safe saxpy is
    bit-identical."""
    n = 256
    results = {}
    for lanes in (1, 2):
        cr = NumberCruncher(devs.subset(lanes), _HALO_SRC)
        try:
            x, y = _halo_args(n)
            x.next_param(y).compute(cr, 73, "sh", n, 32, **_PIPELINED)
            results[lanes] = np.array(y, copy=True)
        finally:
            cr.dispose()
    assert not np.array_equal(results[1], results[2]), (
        "halo-under-partial should corrupt on a real 2-chip split")
    # and the simulator predicts the same divergence pattern
    from tests.kernel_corpus import UNSAFE

    entry = next(e for e in UNSAFE if e.name == "halo_partial")
    assert ground_truth_unsafe(entry, lanes=2)

    safe = {}
    for lanes in (1, 2):
        cr = NumberCruncher(devs.subset(lanes), _SAXPY_SRC)
        try:
            x, y = _halo_args(n)
            x.next_param(y).compute(cr, 74, "saxpy", n, 32, values=(1.5,))
            safe[lanes] = np.array(y, copy=True)
        finally:
            cr.dispose()
    np.testing.assert_array_equal(safe[1], safe[2])


def test_plain_path_widens_the_partial_upload_by_the_reach(devs, monkeypatch):
    """The same halo kernel on the plain path passes the strict gate and
    is bit-identical on two lanes and on one: the lane's slice is uploaded
    with its reach (PR 32)."""
    monkeypatch.setenv("CK_KERNEL_VERIFY", "strict")
    n = 256
    results = {}
    for lanes in (1, 2):
        cr = NumberCruncher(devs.subset(lanes), _HALO_SRC)
        try:
            x, y = _halo_args(n)
            x.next_param(y).compute(cr, 76, "sh", n, 32)
            results[lanes] = np.array(y, copy=True)
        finally:
            cr.dispose()
    np.testing.assert_array_equal(results[1], results[2])


def test_partial_read_fix_is_bit_identical(devs):
    """Satellite pin (the partial_read flag fix): the saxpy
    input under partial_read produces bit-identical results to the
    over-broad full read on a real 2-chip split — the H2D saving is
    free."""
    n = 256
    out = {}
    for label, kw in (("full", dict(read_only=True)),
                      ("partial", dict(partial_read=True, read_only=True))):
        cr = NumberCruncher(devs.subset(2), _SAXPY_SRC)
        try:
            x = ClArray(np.arange(n, dtype=np.float32), name="px", **kw)
            y = ClArray(n, np.float32, name="py", partial_read=True)
            x.next_param(y).compute(cr, 75, "saxpy", n, 32, values=(2.0,))
            out[label] = np.array(y, copy=True)
        finally:
            cr.dispose()
    np.testing.assert_array_equal(out["full"], out["partial"])


_REACH_SRC = """
__kernel void k(__global float* x, __global float* y, int width, int height,
                float scale) {{
    int i = get_global_id(0);
    y[i] = x[{index}];
}}
"""


@pytest.mark.parametrize("index,values,klass,want", [
    # c + k * p, both signs of c, of k and of the value
    ("i - width", dict(width=16), "halo", (16, 0)),
    ("i + width", dict(width=16), "halo", (0, 16)),
    ("i + width", dict(width=-5), "halo", (5, 0)),
    ("i + 2 * width + 1", dict(width=8), "halo", (0, 17)),
    ("i - 3 + width", dict(width=2), "halo", (1, 0)),
    ("i - (width << 1)", dict(width=4), "halo", (8, 0)),
    ("i + width - height", dict(width=4, height=6), "halo", (2, 0)),
    ("i + width - width", dict(width=9), "confined", (0, 0)),
    ("i + 1", {}, "halo", (0, 1)),
    # what stays unbounded: a float added in, a parameter that multiplies
    # the work-item id (a pitch, the code generator's), anything modular
    ("i + (int)scale", dict(scale=2.0), "gather", None),
    ("i * width", dict(width=4), "gather", None),
    ("(i + width) % height", dict(width=4, height=64), "gather", None),
])
def test_reach_of_a_value_parameter_added_inside_an_index(
        index, values, klass, want):
    from cekirdekler_tpu import analysis
    from cekirdekler_tpu.kernel import lang

    (kdef,) = lang.parse_kernels(_REACH_SRC.format(index=index))
    (acc,) = analysis.summarize_kernel(kdef).reads["x"]
    got, width = analysis.classify(acc.av, 1)
    assert got == klass
    if klass == "halo":
        assert analysis.reach_of(acc.av).elements(values, 1) == want
        # a value the launch does not give, or not a whole number: no reach
        if values:
            assert analysis.reach_of(acc.av).elements({}, 1) is None
            broken = {k: v + 0.5 for k, v in values.items()}
            assert analysis.reach_of(acc.av).elements(broken, 1) is None
    elif klass == "confined":
        assert width == 0


def test_verdict_carries_the_reach_where_the_caller_exchanges(devs):
    """``exchange=True`` (the plain path of ``Cores.compute``): the wave
    step's verdict holds no error and carries ``u1``'s reach; without it,
    and for a hazard inside ONE pass, the errors stand."""
    import re

    from cekirdekler_tpu.analysis import FlagRow
    from cekirdekler_tpu.kernel.registry import KernelProgram

    with open(os.path.join(os.path.dirname(__file__), "..", "examples",
                           "wave_equation.py")) as f:
        src = re.search(r'WAVE_SRC = """(.*?)"""', f.read(), re.S).group(1)
    prog = KernelProgram(src)
    part = FlagRow(True, True, True, False, False, False, 1)
    out = FlagRow(False, False, True, False, False, False, 1)
    names, rows = ("waveStep", "rotate"), (part, part, out)
    old = prog.verify(names, rows, window=True)
    assert [f.kind for f in old.errors] == ["partial-read-halo", "window-raw"]
    assert not old.reach and not old.exchanged
    new = prog.verify(names, rows, window=True, exchange=True)
    assert new.ok and new.exchanged == (1,)
    assert new.reads == (0, 1, 2) and new.writes == (0, 1, 2)
    values = dict(width=16384, height=16384, c2=0.22)
    assert new.reach_elements(lambda _k: values) == {1: (16384, 16384)}
    assert {str(r) for _k, _pos, _p, _e, r in new.reach} == {
        "-1", "+1", "-width", "+width"}
    # the writer BEFORE the reader in one pass: nothing lies between the
    # kernels of one compute, so this one stays an error
    swapped = prog.verify(("rotate", "waveStep"), rows, window=True,
                          exchange=True)
    assert [f.kind for f in swapped.errors] == ["window-raw"]


def test_program_verdict_is_cached_per_shape(devs):
    from cekirdekler_tpu.analysis import flag_row
    from cekirdekler_tpu.kernel.registry import KernelProgram

    prog = KernelProgram(_HALO_SRC)
    x, y = _halo_args()
    rows = (flag_row(x.flags), flag_row(y.flags))
    v1 = prog.verify(("sh",), rows)
    v2 = prog.verify(("sh",), rows)
    assert v1 is v2
    assert [f.kind for f in v1.errors] == ["partial-read-halo"]


def test_serve_strict_rejects_and_replays(devs, monkeypatch):
    """Acceptance: under strict verification, serve admission rejects
    the unsafe job with the named ``kernel-unsafe`` reason, records
    the verdict inputs in the replayable admission decision, and the
    rejection replays bit-identically through the ckreplay-verify
    engine."""
    from cekirdekler_tpu.obs.decisions import DECISIONS
    from cekirdekler_tpu.obs.replay import verify_records
    from cekirdekler_tpu.serve.admission import REJECT_KERNEL, ServeRejected
    from cekirdekler_tpu.serve.frontend import ServeFrontend, ServeJob

    monkeypatch.setenv("CK_KERNEL_VERIFY", "strict")
    cr = NumberCruncher(devs.subset(2), _HALO_SRC)
    fe = ServeFrontend(cr, autostart=False)
    try:
        mark = max((r.seq for r in DECISIONS.snapshot()), default=0)
        x, y = _halo_args()
        job = ServeJob(params=[x, y], kernels=("sh",), compute_id=76,
                       global_range=256, local_range=32)
        with pytest.raises(ServeRejected) as ei:
            fe.submit("tenant-a", job)
        assert ei.value.reason == REJECT_KERNEL
        assert ei.value.retry_after_s == 0.0
        recs = [r for r in DECISIONS.snapshot()
                if r.seq > mark and r.kind == "admission"]
        assert recs, "no admission decision recorded"
        rec = recs[-1]
        assert rec.inputs["kernel_unsafe"] is True
        assert rec.inputs["kernel_finding"] == "partial-read-halo"
        assert rec.outputs["reason"] == REJECT_KERNEL
        rep = verify_records(recs)
        assert rep["ok"], rep
        assert rep["replayed"] >= 1
    finally:
        fe.close()
        cr.dispose()


def test_serve_default_mode_admits(devs, monkeypatch):
    """Without strict verification the frontend admits (legacy
    behavior): the kernel gate is opt-in at the serving tier."""
    from cekirdekler_tpu.serve.frontend import ServeFrontend, ServeJob

    monkeypatch.delenv("CK_KERNEL_VERIFY", raising=False)
    cr = NumberCruncher(devs.subset(2), _HALO_SRC)
    fe = ServeFrontend(cr, autostart=False)
    try:
        x, y = _halo_args()
        job = ServeJob(params=[x, y], kernels=("sh",), compute_id=77,
                       global_range=256, local_range=32)
        fut = fe.submit("tenant-b", job)
        fe.step()
        assert fut.result(timeout=10.0)["tenant"] == "tenant-b"
    finally:
        fe.close()
        cr.dispose()


# ---------------------------------------------------------------------------
# 3. CLI lifecycle
# ---------------------------------------------------------------------------

def test_cli_clean_on_head(capsys):
    """THE gate: ckprove exits 0 on HEAD against the checked-in
    baseline — a new split-unsafe kernel anywhere in the scanned
    corpus fails tier-1 right here."""
    rc = ckprove.main([])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "clean" in out


def test_cli_scan_finds_the_repo_kernels():
    """A scan that silently matched nothing would make the gate
    vacuous: the known workload kernels must be inventoried."""
    found = {(rel, src.count("__kernel"))
             for rel, _line, src in ckprove.iter_kernel_sources()}
    names = {rel for rel, _ in found}
    assert any("workloads.py" in p for p in names)
    assert any("examples/" in p or "examples\\" in p for p in names)
    _findings, facts = ckprove.analyze_corpus()
    kernels = {r["kernel"] for r in facts if "arrays" in r}
    assert {"mandelbrot", "nBody", "streamAdd", "wave"} <= kernels


def _corpus_repo(tmp_path, planted: bool):
    d = tmp_path / "repo"
    (d / "cekirdekler_tpu").mkdir(parents=True, exist_ok=True)
    body = (
        'SRC = """\n'
        "__kernel void k(__global float* x, __global float* out) {\n"
        "    int i = get_global_id(0);\n"
        + ("    out[i+1] = x[i];\n" if planted else "    out[i] = x[i];\n")
        + '}\n"""\n'
    )
    (d / "cekirdekler_tpu" / "mod.py").write_text(body)
    return str(d)


def test_cli_ratchet_lifecycle(tmp_path, capsys):
    baseline = str(tmp_path / "b.json")
    root = _corpus_repo(tmp_path, planted=True)
    args = ["--root", root, "--baseline", baseline]

    # (1) new finding fails, naming the kind
    assert ckprove.main(args) == 1
    out = capsys.readouterr().out
    assert "off-partition-write" in out

    # (2) --update-baseline refuses growth without --allow-grow
    assert ckprove.main(args + ["--update-baseline"]) == 1
    assert "REFUSING" in capsys.readouterr().out
    assert ckprove.main(
        args + ["--update-baseline", "--allow-grow"]) == 0
    capsys.readouterr()
    assert ckprove.main(args) == 0  # grandfathered
    capsys.readouterr()

    # (3) --explain renders the rule documentation
    rc = ckprove.main(args + ["--json"])
    doc = json.loads(capsys.readouterr().out)
    fp = doc["grandfathered"][0]["fingerprint"]
    assert rc == 0
    assert ckprove.main(args + ["--explain", fp]) == 0
    assert "partition" in capsys.readouterr().out

    # (4) fixing without shrinking the baseline is stale -> fail
    _corpus_repo(tmp_path, planted=False)
    assert ckprove.main(args) == 1
    assert "STALE" in capsys.readouterr().out

    # (5) the shrink: clean again
    assert ckprove.main(args + ["--update-baseline"]) == 0
    capsys.readouterr()
    assert ckprove.main(args) == 0


def test_cli_source_suppression(tmp_path, capsys):
    baseline = str(tmp_path / "b.json")
    root = _corpus_repo(tmp_path, planted=True)
    mod = os.path.join(root, "cekirdekler_tpu", "mod.py")
    body = open(mod).read().replace(
        "out[i+1] = x[i];",
        "out[i+1] = x[i];  // ckprove: ok ghost cell, range excludes tail")
    open(mod, "w").write(body)
    assert ckprove.main(["--root", root, "--baseline", baseline]) == 0
    assert "clean" in capsys.readouterr().out


def test_cli_runs_without_jax(tmp_path):
    """The run-anywhere discipline: the CLI completes on a rig where
    importing jax raises (the stub package loader path)."""
    import subprocess

    script = (
        "import sys\n"
        "class B:\n"
        "    def find_module(self, name, path=None):\n"
        "        if name=='jax' or name.startswith('jax.'): return self\n"
        "    def load_module(self, name):\n"
        "        raise ImportError('jax broken')\n"
        "sys.meta_path.insert(0, B())\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "import tools.ckprove as ck\n"
        "sys.exit(ck.main([]))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=120, cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_doc_verdict_table_matches_declared_kinds():
    """lint_obs-style two-way check: the verdict table in
    docs/STATIC_ANALYSIS.md lists exactly the declared VERDICT_KINDS —
    a new kind must be documented, a removed one un-documented."""
    doc = ckprove.doc_verdict_kinds()
    assert doc == set(analysis.VERDICT_KINDS), (
        f"doc-only: {doc - set(analysis.VERDICT_KINDS)}, "
        f"code-only: {set(analysis.VERDICT_KINDS) - doc}")


def test_doc_flag_table_matches_flag_row():
    """docs/KERNEL_LANGUAGE.md's flag-soundness table covers every
    flag the verdict reads (FlagRow fields)."""
    text = open(os.path.join(ROOT, "docs", "KERNEL_LANGUAGE.md")).read()
    for fld in analysis.verdict.FlagRow._fields:
        name = ("elements_per_work_item" if fld == "epw" else fld)
        assert f"`{name}`" in text, (
            f"flag {name!r} missing from the KERNEL_LANGUAGE.md "
            "flag-soundness table")
