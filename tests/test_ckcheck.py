"""tools/ckcheck as a tier-1 gate, plus regression tests for the live
findings it surfaced and this PR fixed.

Three layers:

1. **The gate itself** — the analyzer must exit 0 on HEAD against the
   checked-in baseline (re-introducing any fixed finding, or fixing a
   grandfathered one without shrinking the baseline, fails tier-1
   here).
2. **Fixture pins** — each historical bug shape (the PR 6 tracer-lock
   deadlock, the seed-era enqueue/rebalance lost-update race, the
   hot-path registry get-or-create, the RFC-8259 Infinity leak, an
   ABBA lock-order cycle) is planted in ``tests/fixtures_ckcheck/`` and
   must be FOUND, while its clean twin stays silent; plus the
   baseline-ratchet lifecycle (new finding fails → --update-baseline
   refuses growth without --allow-grow → fixing shrinks).
3. **Runtime regressions** — behavior tests for the fixes: bench-dict
   writes hold the worker lock, the fused deferral allocates no
   telemetry when the tracer is off, export paths emit strict
   RFC-8259 JSON, and ``ClTaskPool.feed`` no longer nests two pool
   locks.
"""

import json
import math
import os
import sys
import threading

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
FIXTURES = os.path.join(HERE, "fixtures_ckcheck")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools.ckcheck import (  # noqa: E402
    AnalyzerConfig,
    load_baseline,
    lock_order_edges,
    ratchet,
    run_passes,
    save_baseline,
    scan_package,
)
from tools.ckcheck.cli import main as ckcheck_main  # noqa: E402


def _fixture_findings(cfg=None):
    pkg = scan_package(FIXTURES, pkg_name="fixtures_ckcheck",
                       repo_root=ROOT)
    cfg = cfg or AnalyzerConfig(
        hot_roots=("hot_bad.Engine.defer", "hot_ok.Engine.defer"),
    )
    return run_passes(pkg, cfg)


def _by_rule(findings, rule):
    return [f for f in findings if f.rule == rule]


# ---------------------------------------------------------------------------
# 1. the gate itself
# ---------------------------------------------------------------------------

def test_live_tree_is_clean_against_baseline(capsys):
    """THE gate: ckcheck exits 0 on HEAD.  A new concurrency/hot-path/
    invariant finding anywhere in cekirdekler_tpu/ or
    tools/ fails tier-1 right here with the finding printed."""
    rc = ckcheck_main([])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "clean" in out


def test_live_lock_order_graph_is_nonempty():
    # a graph that silently resolved nothing would make the deadlock
    # pass vacuous — the known Worker.lock -> scheduler lock edge must be
    # present (a lane's phase leaves its records in the window inside)
    pkg = scan_package(os.path.join(ROOT, "cekirdekler_tpu"))
    edges = set(lock_order_edges(pkg))
    assert ("core.worker.Worker.lock", "core.window.Window.lock") in edges
    assert len(edges) >= 3


# ---------------------------------------------------------------------------
# 2a. fixture pins: each historical shape is FOUND, its twin is silent
# ---------------------------------------------------------------------------

def test_fixture_tracer_deadlock_shape_found():
    found = _by_rule(_fixture_findings(), "reacquire")
    assert any("deadlock_bad" in f.path for f in found), found
    assert not any("deadlock_ok" in f.path for f in found), found


def test_fixture_lost_update_race_found():
    found = _by_rule(_fixture_findings(), "mixed-guard")
    assert any(f.subject == "race_bad.Scheduler.pending" for f in found), found
    assert not any("race_ok" in f.subject for f in found), found


def test_fixture_hot_get_or_create_found():
    found = _by_rule(_fixture_findings(), "get-or-create")
    assert any("hot_bad" in f.subject for f in found), found
    assert not any("hot_ok" in f.subject for f in found), found


def test_fixture_order_cycle_found():
    found = _by_rule(_fixture_findings(), "order-cycle")
    assert any("cycle_bad._lock_a" in f.subject for f in found), found
    assert not any("cycle_ok" in f.subject for f in found), found


def test_fixture_unbounded_blocking_found():
    """Pass 5: the zero-arg get()/wait()/join() shutdown-hang shapes
    are FOUND in blocking_bad; the bounded/annotated twin is silent."""
    found = _by_rule(_fixture_findings(), "unbounded-blocking")
    methods = {(f.path.rsplit("/", 1)[-1], f.subject.rsplit(":", 1)[-1])
               for f in found}
    assert ("blocking_bad.py", "get") in methods, found
    assert ("blocking_bad.py", "wait") in methods, found
    assert ("blocking_bad.py", "join") in methods, found
    assert not any("blocking_ok" in f.path for f in found), found


def test_blocking_skips_bounded_and_operand_calls(tmp_path):
    """str.join(parts) / dict.get(key) / wait(timeout) carry operands
    or bounds — never findings (the rule is the ZERO-arg form)."""
    (tmp_path / "mod.py").write_text(
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._cond = threading.Condition()\n"
        "        self._t = threading.Thread(target=min)\n"
        "        self._t.start()\n"
        "    def go(self, d, parts):\n"
        "        s = ' '.join(parts)\n"
        "        v = d.get('k')\n"
        "        with self._cond:\n"
        "            self._cond.wait(0.5)\n"
        "        self._t.join(timeout=1.0)\n"
        "        return s, v\n")
    pkg = scan_package(str(tmp_path), pkg_name="fx",
                       repo_root=str(tmp_path))
    findings = run_passes(pkg, AnalyzerConfig())
    assert not _by_rule(findings, "unbounded-blocking"), findings


def test_fixture_invariants_found():
    findings = _fixture_findings()
    ju = _by_rule(findings, "json-unsafe")
    assert any("invariant_bad" in f.path for f in ju), ju
    assert not any("invariant_ok" in f.path for f in ju), ju
    hl = _by_rule(findings, "headline-last")
    assert any("invariant_bad" in f.path for f in hl), hl
    assert not any("invariant_ok" in f.path for f in hl), hl


def test_cli_fails_naming_each_historical_shape(tmp_path, monkeypatch,
                                                capsys):
    """The acceptance demo: re-introducing each historical bug shape in
    a fixture module makes `python -m tools.ckcheck` exit nonzero,
    NAMING the finding — the PR 6 tracer-lock deadlock (reacquire), the
    seed-era lost-update race (mixed-guard), and the hot-path
    get-or-create."""
    import tools.ckcheck.cli as cli

    monkeypatch.setattr(cli, "_repo_extra_paths", lambda: [])
    monkeypatch.setattr(cli, "repo_config", lambda: AnalyzerConfig(
        hot_roots=("hot_bad.Engine.defer", "hot_ok.Engine.defer")))
    rc = cli.main(["--root", FIXTURES,
                   "--baseline", str(tmp_path / "empty.json")])
    out = capsys.readouterr().out
    assert rc == 1
    assert "reacquire" in out and "deadlock_bad" in out
    assert "mixed-guard" in out and "race_bad.Scheduler.pending" in out
    assert "get-or-create" in out and "hot_bad" in out


def test_fixture_suppression_comment_silences(tmp_path):
    bad = open(os.path.join(FIXTURES, "race_bad.py")).read()
    bad = bad.replace(
        "        self.pending = self.pending // 2  # unlocked RMW: lost update",
        "        # ckcheck: ok rebalance runs quiescent in this variant\n"
        "        self.pending = self.pending // 2",
    )
    (tmp_path / "race_bad.py").write_text(bad)
    pkg = scan_package(str(tmp_path), pkg_name="fx", repo_root=str(tmp_path))
    findings = run_passes(pkg, AnalyzerConfig())
    assert not _by_rule(findings, "mixed-guard"), findings


# ---------------------------------------------------------------------------
# 2b. the ratchet lifecycle
# ---------------------------------------------------------------------------

def _mini_repo(tmp_path, planted: bool):
    d = tmp_path / "pkg"
    d.mkdir(exist_ok=True)
    body = open(os.path.join(
        FIXTURES, "race_bad.py" if planted else "race_ok.py")).read()
    (d / "mod.py").write_text(body)
    pkg = scan_package(str(d), pkg_name="pkg", repo_root=str(tmp_path))
    return run_passes(pkg, AnalyzerConfig())


def test_ratchet_lifecycle(tmp_path):
    baseline_path = str(tmp_path / "baseline.json")

    # (1) a finding with an empty baseline is NEW -> the run must fail
    findings = _mini_repo(tmp_path, planted=True)
    assert findings
    new, grand, stale = ratchet(findings, load_baseline(baseline_path))
    assert new and not grand and not stale

    # (2) grandfather it; the same findings are now covered
    save_baseline(baseline_path, findings)
    new, grand, stale = ratchet(findings, load_baseline(baseline_path))
    assert not new and grand and not stale

    # (3) fixing the finding WITHOUT shrinking the baseline is stale ->
    # the run must fail until --update-baseline rewrites it
    fixed = _mini_repo(tmp_path, planted=False)
    new, grand, stale = ratchet(fixed, load_baseline(baseline_path))
    assert not new and stale

    # (4) the shrink: rewrite from current findings -> clean
    save_baseline(baseline_path, fixed)
    new, grand, stale = ratchet(fixed, load_baseline(baseline_path))
    assert not new and not grand and not stale


def test_update_baseline_refuses_growth_without_allow_grow(
        tmp_path, monkeypatch, capsys):
    """CLI semantics: --update-baseline with NEW findings refuses unless
    --allow-grow rides along (adding debt is deliberate, never a
    reflex)."""
    import tools.ckcheck.cli as cli

    d = tmp_path / "pkg"
    d.mkdir()
    (d / "mod.py").write_text(
        open(os.path.join(FIXTURES, "race_bad.py")).read())
    monkeypatch.setattr(cli, "_repo_extra_paths", lambda: [])
    monkeypatch.setattr(
        cli, "repo_config", lambda: AnalyzerConfig())
    baseline = str(tmp_path / "b.json")
    args = ["--root", str(d), "--baseline", baseline]

    assert cli.main(args) == 1                       # new finding fails
    assert cli.main(args + ["--update-baseline"]) == 1   # refuses growth
    assert "REFUSING" in capsys.readouterr().out
    assert cli.main(
        args + ["--update-baseline", "--allow-grow"]) == 0
    assert cli.main(args) == 0                       # grandfathered now

    # fingerprints survive line drift: prepend a comment, still clean
    (d / "mod.py").write_text(
        "# an unrelated edit above the finding\n"
        + open(os.path.join(FIXTURES, "race_bad.py")).read())
    assert cli.main(args) == 0


def test_explain_prints_rule_documentation(tmp_path, monkeypatch, capsys):
    import tools.ckcheck.cli as cli

    d = tmp_path / "pkg"
    d.mkdir()
    (d / "mod.py").write_text(
        open(os.path.join(FIXTURES, "race_bad.py")).read())
    monkeypatch.setattr(cli, "_repo_extra_paths", lambda: [])
    monkeypatch.setattr(cli, "repo_config", lambda: AnalyzerConfig())
    baseline = str(tmp_path / "b.json")
    rc = cli.main(["--root", str(d), "--baseline", baseline, "--json"])
    out = capsys.readouterr().out
    assert rc == 1
    fp = json.loads(out)["new"][0]["fingerprint"]
    rc = cli.main(["--root", str(d), "--baseline", baseline,
                   "--explain", fp])
    out = capsys.readouterr().out
    assert rc == 0
    assert "lost update" in out or "read-modify-write" in out


# ---------------------------------------------------------------------------
# 2c. the dynamic lock-order witness
# ---------------------------------------------------------------------------

def test_witness_records_nested_named_acquisitions():
    from tools.ckcheck.witness import Witness, _NamedLock

    w = Witness({})
    a = _NamedLock(threading.Lock(), "pkg.A", w)
    b = _NamedLock(threading.Lock(), "pkg.B", w)
    with a:
        with b:
            pass
    with b:  # second, non-nested acquisition adds no edge
        pass
    assert w.dynamic_edges() == {("pkg.A", "pkg.B")}
    rep = w.report({("pkg.A", "pkg.B"), ("pkg.X", "pkg.Y")})
    assert rep["dynamic_only"] == []
    assert rep["static_only"] == [["pkg.X", "pkg.Y"]]


def test_witness_install_wraps_package_locks():
    from tools.ckcheck.witness import install, _NamedLock

    w = install(os.path.join(ROOT, "cekirdekler_tpu"))
    try:
        from cekirdekler_tpu.metrics.registry import MetricsRegistry

        reg = MetricsRegistry()
        assert isinstance(reg._lock, _NamedLock)
        with reg._lock:
            pass
        # a lock created OUTSIDE the package stays a plain lock
        plain = threading.Lock()
        assert not isinstance(plain, _NamedLock)
        assert "metrics.registry.MetricsRegistry._lock" in \
            w._seen_locks
    finally:
        w.uninstall()


# ---------------------------------------------------------------------------
# 3. regression tests for the live findings fixed in this PR
# ---------------------------------------------------------------------------

class _LockAssertingDict(dict):
    """A bench dict that refuses unlocked writes: every mutation must
    hold the owning worker's RLock (the ckcheck mixed-guard contract)."""

    def __init__(self, lock, *a):
        super().__init__(*a)
        self._lock = lock

    def _check(self):
        assert self._lock._is_owned(), (
            "bench dict written without holding the worker lock")

    def __setitem__(self, k, v):
        self._check()
        super().__setitem__(k, v)

    def update(self, *a, **kw):
        self._check()
        super().update(*a, **kw)


@pytest.fixture(scope="module")
def devs():
    from cekirdekler_tpu.hardware import platforms

    return platforms().cpus()


_INC = """
__kernel void inc(__global float* a) {
    int i = get_global_id(0);
    a[i] = a[i] + 1.0f;
}
"""


def test_bench_dict_writes_hold_worker_lock(devs):
    """PR 7 fix: the barrier's bench feed, the zero-share decay, and the
    flush drain's transfer feed all hold w.lock now — instrumented
    dicts assert it on every write through a real enqueue window."""
    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core import NumberCruncher

    cr = NumberCruncher(devs.subset(2), _INC)
    try:
        for w in cr.cores.workers:
            w.benchmarks = _LockAssertingDict(w.lock, w.benchmarks)
            w.transfer_benchmarks = _LockAssertingDict(
                w.lock, w.transfer_benchmarks)
        x = ClArray(np.zeros(4096, np.float32), name="ck_x")
        x.partial_read = True
        cr.enqueue_mode = True
        for phase in range(3):
            for _ in range(4):
                x.compute(cr, 901, "inc", 4096, 64)
            cr.barrier()          # bench feed must lock
        cr.enqueue_mode = False   # flush: transfer feed must lock
        np.testing.assert_array_equal(np.asarray(x), 12.0)
    finally:
        cr.dispose()


def test_fused_defer_records_no_telemetry_when_disabled(devs):
    """PR 7 hot-path fix: with the tracer off, the deferral must not
    even CALL TRACER.record (the tag concat allocated per deferral
    before the guard)."""
    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core import NumberCruncher
    from cekirdekler_tpu.trace import spans

    calls = []
    orig = spans.TRACER.record
    cr = NumberCruncher(devs.subset(1), _INC)
    try:
        assert not spans.TRACER.enabled
        x = ClArray(np.zeros(1024, np.float32), name="ck_t")
        x.partial_read = True
        cr.enqueue_mode = True
        x.compute(cr, 902, "inc", 1024, 64)  # per-call (engage seed)
        x.compute(cr, 902, "inc", 1024, 64)  # engages
        spans.TRACER.record = lambda *a, **kw: calls.append(a)
        for _ in range(6):                   # pure deferrals
            x.compute(cr, 902, "inc", 1024, 64)
        assert cr.fused_stats["deferred_iters"] >= 6
        assert calls == [], (
            "fused deferral called TRACER.record with the tracer off")
    finally:
        spans.TRACER.record = orig
        cr.enqueue_mode = False
        cr.dispose()


def test_taskpool_feed_does_not_nest_pool_locks():
    """PR 7 deadlock fix: feed() snapshots BEFORE locking, so
    self-feeding (the degenerate same-instance case of the ABBA shape)
    completes instead of deadlocking on the non-reentrant lock."""
    from cekirdekler_tpu.pipeline.pool import ClTask, ClTaskPool

    pool = ClTaskPool([ClTask()])
    done = []

    def run():
        pool.feed(pool)  # pre-fix: self-deadlock, forever
        done.append(len(pool))

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout=5.0)
    assert done == [2], "feed() still nests ClTaskPool locks"


def test_debug_endpoints_emit_strict_rfc8259_json():
    """PR 7 invariant fix (the generalized /healthz bug): an inf gauge
    anywhere in the registry must come back as null, never as the bare
    `Infinity` token a strict parser rejects."""
    import urllib.request

    from cekirdekler_tpu.metrics import REGISTRY
    from cekirdekler_tpu.obs.debugserver import DebugServer

    g = REGISTRY.gauge("ck_lane_health", "verdict", lane=998877)
    srv = DebugServer(cores=None, port=0)
    try:
        g.set(float("inf"))
        body = urllib.request.urlopen(srv.url + "/flightz").read().decode()

        def reject(_):  # json.loads accepts Infinity unless told not to
            raise AssertionError("non-RFC-8259 constant in payload")

        doc = json.loads(body, parse_constant=reject)
        assert doc["metrics"]["gauges"]['ck_lane_health{lane="998877"}'] \
            is None
    finally:
        g.set(0.0)
        srv.close()


def test_json_safe_sanitizes_everything():
    from cekirdekler_tpu.utils.jsonsafe import dumps_safe, json_safe

    weird = {
        "inf": float("inf"),
        "ninf": float("-inf"),
        "nan": float("nan"),
        "np_scalar": np.float32("inf"),
        "np_int": np.int64(7),
        "np_arr": np.asarray([1.0, float("inf")]),
        np.int32(3): ("tuple", {"nested_nan": float("nan")}),
        "plain": [1, "x", True, None, 2.5],
    }
    out = json_safe(weird)
    assert out["inf"] is None and out["ninf"] is None and out["nan"] is None
    assert out["np_scalar"] is None
    assert out["np_int"] == 7
    assert out["np_arr"] == [1.0, None]
    assert out["3"] == ["tuple", {"nested_nan": None}]
    assert out["plain"] == [1, "x", True, None, 2.5]

    def reject(_):
        raise AssertionError("non-RFC-8259 constant survived json_safe")

    assert json.loads(dumps_safe(weird), parse_constant=reject)
    # cycles degrade to a placeholder instead of recursing forever
    cyc: dict = {}
    cyc["self"] = cyc
    assert json_safe(cyc) == {"self": "<cycle>"}
    # finite floats pass through untouched
    assert json_safe({"x": 1.5}) == {"x": 1.5}
    assert math.isfinite(json.loads(dumps_safe({"v": 2.25}))["v"])
