"""tools/regress.py — the bench regression sentinel's acceptance gates
(ISSUE 4): nonzero on an injected 20% headline regression, nonzero on a
bare-null watched section WITH the starvation reason surfaced, zero on
an unchanged artifact pair; plus the truncated-tail recovery and the
noise-aware tolerance widening.  Also pins the bench.SectionScheduler
side of the contract: skipped/starved sections write structured
``{"null_reason", "budget_spent_s"}`` records into the artifact.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

spec = importlib.util.spec_from_file_location(
    "ck_regress", os.path.join(ROOT, "tools", "regress.py"))
regress = importlib.util.module_from_spec(spec)
spec.loader.exec_module(regress)


HEADLINE = {
    "mandelbrot_mpix": 240.0,
    "vs_tuned_loop": 1.0,
    "repeat_mode_mpix": 430.0,
    "flash_T8192_mfu_default": 0.30,
    "flash_T8192_speedup_highest": 1.2,
    "nbody_e2e_enqueue_gpairs": 15.0,
    "dispatch_floor_collapse": 5.0,
    "overlap_balanced_raw": 0.80,
    "serve_p50_ms": 8.0,
    "serve_p99_ms": 40.0,
    "serve_goodput_rps": 400.0,
    "serve_coalesce_ratio": 4.0,
    "serve_chaos_goodput_frac": 0.9,
    "serve_chaos_p99_ms": 60.0,
    "serve_p99_queue_frac": 0.5,
    "serve_p99_device_frac": 0.4,
    "fabric_chaos_goodput_frac": 0.8,
    "drain_recover_ms": 900.0,
    "rejoin_converge_iters": 4.0,
    "cold_start_warm_speedup": 20.0,
    "hetero_speedup_vs_best_homog": 1.12,
}


def _art(headline, errors=None, sections=None):
    return {"path": "<mem>", "headline": headline, "errors": errors,
            "sections": sections}


def test_unchanged_pair_is_healthy():
    v = regress.diff_headlines(_art(HEADLINE), _art(dict(HEADLINE)))
    assert v["ok"] and v["exit_code"] == 0
    assert v["checked"] == len(regress.WATCHED_KEYS)
    assert v["findings"] == []


def test_injected_20pct_regression_fails_with_exit_2():
    bad = dict(HEADLINE)
    bad["flash_T8192_mfu_default"] *= 0.8 - 1e-6
    v = regress.diff_headlines(_art(HEADLINE), _art(bad))
    assert not v["ok"] and v["exit_code"] == 2
    keys = [f["key"] for f in v["findings"]]
    assert keys == ["flash_T8192_mfu_default"]
    assert v["findings"][0]["drop_frac"] > 0.19


def test_lower_direction_latency_regression_fails():
    """The serve latency keys watch LOWER-is-better: p50 doubling is a
    regression; p50 halving is an improvement and never fails."""
    bad = dict(HEADLINE)
    bad["serve_p50_ms"] *= 2.0
    v = regress.diff_headlines(_art(HEADLINE), _art(bad))
    assert not v["ok"] and v["exit_code"] == 2
    assert [f["key"] for f in v["findings"]] == ["serve_p50_ms"]
    good = dict(HEADLINE)
    good["serve_p50_ms"] *= 0.5
    good["serve_goodput_rps"] *= 2.0
    v = regress.diff_headlines(_art(HEADLINE), _art(good))
    assert v["ok"] and v["findings"] == []


def test_improvements_never_fail():
    # "better" respects each key's direction: higher-is-better keys
    # double, lower-is-better keys (the serve latencies) halve
    lower = {k for k, _a, d, _t in regress.WATCHED_KEYS if d == "lower"}
    better = {
        k: (v * 0.5 if k in lower else v * 2) for k, v in HEADLINE.items()
    }
    v = regress.diff_headlines(_art(HEADLINE), _art(better))
    assert v["ok"]


def test_bare_null_watched_key_is_hard_failure_with_reason():
    starved = dict(HEADLINE)
    starved["flash_T8192_mfu_default"] = None
    v = regress.diff_headlines(
        _art(HEADLINE),
        _art(starved, errors={
            "flash_train": "skipped: 1500s bench budget spent"}),
    )
    assert v["exit_code"] == 3
    f = v["findings"][0]
    assert f["kind"] == "starved" and f["key"] == "flash_T8192_mfu_default"
    assert "budget spent" in f["reason"]


def test_null_reason_record_preferred_over_errors_map():
    starved = dict(HEADLINE)
    starved["dispatch_floor_collapse"] = None
    sections = {
        "dispatch_floor": {
            "null_reason": "skipped: budget spent", "budget_spent_s": 1432.1,
        },
    }
    v = regress.diff_headlines(
        _art(HEADLINE), _art(starved, sections=sections))
    assert v["exit_code"] == 3
    assert "budget_spent_s=1432.1" in v["findings"][0]["reason"]


def test_hetero_key_watched_and_exactness_starves():
    """ISSUE 20: hetero_speedup_vs_best_homog is regression-watched
    (higher is better, wide 30% floor) and exactness-gated — the bench
    nulls it whenever the four arms' digests diverge, and the sentinel
    must surface that null as STARVED with the hetero section's reason,
    not as a silent pass."""
    assert any(k == "hetero_speedup_vs_best_homog"
               for k, _a, _d, _t in regress.WATCHED_KEYS)
    assert regress.KEY_SECTION["hetero_speedup_vs_best_homog"] == "hetero"
    bad = dict(HEADLINE)
    bad["hetero_speedup_vs_best_homog"] *= 0.6  # past the 30% floor
    v = regress.diff_headlines(_art(HEADLINE), _art(bad))
    assert not v["ok"] and v["exit_code"] == 2
    assert [f["key"] for f in v["findings"]] == [
        "hetero_speedup_vs_best_homog"]
    starved = dict(HEADLINE)
    starved["hetero_speedup_vs_best_homog"] = None
    sections = {"hetero": {
        "null_reason": "inexact: mixed arm digest diverged",
        "budget_spent_s": 12.0}}
    v = regress.diff_headlines(
        _art(HEADLINE), _art(starved, sections=sections))
    assert v["exit_code"] == 3
    f = v["findings"][0]
    assert f["kind"] == "starved"
    assert f["key"] == "hetero_speedup_vs_best_homog"
    assert "digest diverged" in f["reason"]


def test_missing_headline_block_entirely_is_starved():
    v = regress.diff_headlines(_art(HEADLINE), _art(None))
    assert v["exit_code"] == 3
    assert v["findings"][0]["key"] == "headline"


def test_key_aliases_bridge_artifact_generations():
    old = dict(HEADLINE)
    old["nbody_e2e_gpairs"] = old.pop("nbody_e2e_enqueue_gpairs")
    v = regress.diff_headlines(_art(old), _art(HEADLINE))
    assert v["ok"]
    # and a drop through the alias still fires
    bad = dict(HEADLINE)
    bad["nbody_e2e_enqueue_gpairs"] *= 0.5
    v = regress.diff_headlines(_art(old), _art(bad))
    assert v["exit_code"] == 2


def test_noisy_trajectory_widens_tolerance_stable_one_does_not():
    hist_noisy = [
        _art({**HEADLINE, "mandelbrot_mpix": m})
        for m in (160.0, 300.0, 170.0, 290.0, 240.0)
    ]
    hist_stable = [
        _art({**HEADLINE, "mandelbrot_mpix": m})
        for m in (238.0, 241.0, 240.0, 239.5, 240.0)
    ]
    cand = dict(HEADLINE)
    cand["mandelbrot_mpix"] *= 0.82  # 18% drop: above the 10% floor
    v = regress.diff_headlines(
        _art(HEADLINE), _art(cand), history=hist_noisy)
    assert v["ok"], v  # link-weather key: 2x CV tolerance absorbs it
    v = regress.diff_headlines(
        _art(HEADLINE), _art(cand), history=hist_stable)
    assert v["exit_code"] == 2  # historically stable key: the drop is real


def test_extract_tail_object_from_truncated_json():
    """Driver artifacts hold only the LAST 2000 chars of output; the
    headline block prints last so it survives — recovery must work from
    text whose front is cut mid-object."""
    full = json.dumps({
        "metric": "x", "value": 1.0, "big": list(range(500)),
        "errors": {"dtype_matrix": "skipped: budget"},
        "headline": {"mandelbrot_mpix": 240.0, "n_errors": 1},
    })
    tail = full[-300:]
    h = regress.extract_tail_object(tail, "headline")
    assert h == {"mandelbrot_mpix": 240.0, "n_errors": 1}
    e = regress.extract_tail_object(tail, "errors")
    assert e == {"dtype_matrix": "skipped: budget"}
    assert regress.extract_tail_object("no such thing", "headline") is None
    # braces inside strings must not confuse the scanner
    tricky = '"headline": {"note": "a { b } c", "v": 2}'
    assert regress.extract_tail_object(tricky, "headline")["v"] == 2


def test_starvation_reason_survives_driver_tail_truncation():
    """The end-to-end tail contract: a driver artifact whose front
    (including the annotated sections AND a large metrics snapshot) is
    cut must still yield the starvation reason — errors/null_sections/
    headline print last, and the sentinel reads null_sections first."""
    doc = {
        "metric": "x",
        "flash_train": {"null_reason": "skipped: budget", "x": 1},
        "metrics": {"counters": {f"ck_big_{i}": i for i in range(200)}},
        "regression": {"ok": True},
        "errors": {"flash_train": "skipped: budget"},
        "null_sections": {"flash_train": {
            "null_reason": "skipped: budget", "budget_spent_s": 1430.0}},
        "headline": {**HEADLINE, "flash_T8192_mfu_default": None},
    }
    tail = json.dumps(doc)[-2000:]
    art = {"n": 6, "cmd": "python bench.py", "rc": 0, "tail": tail,
           "parsed": None}
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(art, f)
        p = f.name
    loaded = regress.load_headline(p)
    os.unlink(p)
    assert loaded["headline"]["mandelbrot_mpix"] == HEADLINE[
        "mandelbrot_mpix"]
    assert loaded["null_sections"]["flash_train"]["budget_spent_s"] == 1430.0
    v = regress.diff_headlines(_art(HEADLINE), loaded)
    assert v["exit_code"] == 3
    assert "budget_spent_s=1430.0" in v["findings"][0]["reason"]


def test_artifact_round_ordering_is_numeric(tmp_path):
    """r100 is newer than r99 — lexicographic basename ordering would
    gate the fresh artifact against the wrong round."""
    for r, m in (("98", 240.0), ("99", 240.0), ("100", 120.0)):
        (tmp_path / f"BENCH_r{r}.json").write_text(json.dumps(
            {"headline": {**HEADLINE, "mandelbrot_mpix": m}}))
    paths = [os.path.basename(p)
             for p in regress._artifact_paths(str(tmp_path))]
    assert paths == ["BENCH_r98.json", "BENCH_r99.json", "BENCH_r100.json"]
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "regress.py"),
         "--against", str(tmp_path / "BENCH_r99.json"),
         "--root", str(tmp_path)],
        capture_output=True, text=True,
    )
    # r100 (the 50% drop) must be the picked candidate — exit 2
    assert r.returncode == 2, r.stdout + r.stderr
    assert "BENCH_r100" in r.stdout or "mandelbrot" in r.stdout


def _driver_artifact(tmp_path, name="BENCH_r05.json"):
    """A synthetic driver-format artifact: the wrapper the driver wrote
    around a bench run, whose ``tail`` is the LAST 2000 characters of
    the one JSON line (front cut mid-object, headline block last)."""
    doc = {
        "metric": "mandelbrot_throughput", "value": 240.0,
        "lowering_faceoff": {"wave_stencil": {"x": list(range(400))}},
        "errors": {"dtype_matrix": "skipped: 1500s bench budget spent"},
        "headline": dict(HEADLINE),
    }
    p = tmp_path / name
    p.write_text(json.dumps({
        "n": 5, "cmd": "python bench.py", "rc": 0,
        "tail": json.dumps(doc)[-2000:], "parsed": None}))
    return str(p)


def test_load_headline_driver_format_artifact(tmp_path):
    art = regress.load_headline(_driver_artifact(tmp_path))
    assert isinstance(art["headline"], dict)
    assert "mandelbrot_mpix" in art["headline"]
    assert isinstance(art["errors"], dict)


def test_cli_acceptance_pair(tmp_path):
    """The acceptance criterion end-to-end through the CLI: a driver-
    format baseline vs (a) itself → 0, (b) 20% injected regression →
    nonzero, (c) a bare-null section → nonzero."""
    base = _driver_artifact(tmp_path)
    h = dict(regress.load_headline(base)["headline"])

    def run(candidate_doc):
        p = tmp_path / "cand.json"
        p.write_text(json.dumps(candidate_doc))
        return subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "regress.py"),
             "--against", base, "--candidate", str(p)],
            capture_output=True, text=True,
        )

    ok = run({"headline": h, "errors": {}})
    assert ok.returncode == 0, ok.stdout + ok.stderr

    bad = dict(h)
    bad["mandelbrot_mpix"] *= 0.79
    r = run({"headline": bad, "errors": {}})
    assert r.returncode == 2, r.stdout + r.stderr
    assert "REGRESSION" in r.stdout

    starved = dict(h)
    starved["flash_T8192_mfu_default"] = None
    r = run({"headline": starved,
             "errors": {"flash_train": "skipped: budget spent"}})
    assert r.returncode == 3, r.stdout + r.stderr
    assert "STARVED" in r.stdout and "budget spent" in r.stdout


def test_cli_candidate_excluded_from_noise_model(tmp_path):
    """A regressed candidate must not feed the trajectory noise model:
    before the fix, a 30% drop inflated the CV enough to widen its own
    tolerance past the drop and exit 0."""
    for r, m in (("01", 240.0), ("02", 240.0), ("03", 239.0)):
        (tmp_path / f"BENCH_r{r}.json").write_text(json.dumps(
            {"headline": {**HEADLINE, "mandelbrot_mpix": m}}))
    (tmp_path / "BENCH_r04.json").write_text(json.dumps(
        {"headline": {**HEADLINE, "mandelbrot_mpix": 168.0}}))  # -30%
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "regress.py"),
         "--against", str(tmp_path / "BENCH_r03.json"),
         "--root", str(tmp_path)],
        capture_output=True, text=True,
    )
    assert r.returncode == 2, r.stdout + r.stderr
    assert "mandelbrot_mpix" in r.stdout


def test_cli_default_candidate_never_diffs_backwards(tmp_path):
    """--against the NEWEST artifact with no --candidate must refuse
    (a time-reversed diff reads improvements as regressions), not
    silently pick an older round."""
    for r, m in (("01", 200.0), ("02", 240.0)):
        (tmp_path / f"BENCH_r{r}.json").write_text(json.dumps(
            {"headline": {**HEADLINE, "mandelbrot_mpix": m}}))
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "regress.py"),
         "--against", str(tmp_path / "BENCH_r02.json"),
         "--root", str(tmp_path)],
        capture_output=True, text=True,
    )
    assert r.returncode == 1
    assert "no artifact newer" in r.stderr
    # a baseline outside BENCH_r<N> naming has no round to compare:
    # refuse (the -1 fallback key would mark every artifact "newer")
    (tmp_path / "fresh.json").write_text(json.dumps(
        {"headline": dict(HEADLINE)}))
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "regress.py"),
         "--against", str(tmp_path / "fresh.json"),
         "--root", str(tmp_path)],
        capture_output=True, text=True,
    )
    assert r.returncode == 1
    assert "BENCH_r<N> naming" in r.stderr
    # and with an older baseline the newer artifact is picked forward
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "regress.py"),
         "--against", str(tmp_path / "BENCH_r01.json"),
         "--root", str(tmp_path)],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stdout + r.stderr


def test_bench_epilogue_embeds_verdict(tmp_path):
    root = str(tmp_path)
    base = {"headline": dict(HEADLINE), "errors": {}}
    (tmp_path / "BENCH_r90.json").write_text(json.dumps(base))
    result = {"headline": dict(HEADLINE), "errors": {}}
    v = regress.bench_epilogue(result, repo_root=root)
    assert v["ok"] and v["against"] == "BENCH_r90.json"
    result_bad = {"headline": {**HEADLINE,
                               "nbody_e2e_enqueue_gpairs": 1.0},
                  "errors": {}}
    v = regress.bench_epilogue(result_bad, repo_root=root)
    assert v["exit_code"] == 2
    # no artifacts -> no verdict, never a crash
    assert regress.bench_epilogue(result, repo_root=str(tmp_path / "x")) is None


def test_bench_epilogue_skips_headline_less_newest_artifact(tmp_path):
    """A truncated previous round (no recoverable headline) must not
    silently disable the sentinel (0 keys checked would read ok:true);
    the epilogue falls back to the newest artifact WITH a headline."""
    (tmp_path / "BENCH_r90.json").write_text(json.dumps(
        {"headline": dict(HEADLINE)}))
    (tmp_path / "BENCH_r91.json").write_text(json.dumps(
        {"n": 91, "rc": 1, "tail": "crashed before the tail block",
         "parsed": None}))
    bad = {"headline": {**HEADLINE,
                        "nbody_e2e_enqueue_gpairs": 1.0}, "errors": {}}
    v = regress.bench_epilogue(bad, repo_root=str(tmp_path))
    assert v["exit_code"] == 2 and v["against"] == "BENCH_r90.json"
    # and when NO artifact has a headline: ok None, never ok true
    only_bad = tmp_path / "only"
    only_bad.mkdir()
    (only_bad / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "rc": 1, "tail": "x", "parsed": None}))
    v = regress.bench_epilogue(bad, repo_root=str(only_bad))
    assert v["ok"] is None and "no on-disk artifact" in v["error"]


# ---------------------------------------------------------------------------
# bench.SectionScheduler: structured null records (the producer side)
# ---------------------------------------------------------------------------

def _bench():
    sys.path.insert(0, ROOT)
    import bench

    return bench


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_scheduler_records_structured_skip_reason():
    bench = _bench()
    clock = _Clock()
    s = bench.SectionScheduler(100.0, {"dtype_matrix": 30.0}, clock=clock)
    clock.t = 95.0
    assert s.run("overlap", lambda: "x", default=None) is None
    rec = s.skips["overlap"]
    assert "skipped" in rec["null_reason"]
    assert rec["budget_spent_s"] == 95.0


def test_scheduler_records_structured_exception_reason():
    bench = _bench()
    s = bench.SectionScheduler(100.0, {})

    def boom():
        raise RuntimeError("link died")

    assert s.run("flash_train", boom, default=None) is None
    rec = s.skips["flash_train"]
    assert rec["null_reason"].startswith("RuntimeError")
    assert "budget_spent_s" in rec


def test_finalize_result_tail_order_and_embeds():
    """The artifact epilogue: null records written, metrics snapshot +
    regression verdict embedded, headline LAST (tail survival) with
    regression_ok mirrored into it."""
    bench = _bench()
    clock = _Clock()
    s = bench.SectionScheduler(100.0, {"dtype_matrix": 60.0}, clock=clock)
    clock.t = 99.0
    dt = s.run("dtype_matrix_like", lambda: None, default=None)
    result = {
        "metric": "mandelbrot_throughput",
        "dtype_matrix_like": dt,
        "errors": s.errors,
        "headline": dict(HEADLINE),
    }
    out = bench.finalize_result(result, s)
    keys = list(out)
    # tail-critical order: the (possibly large) metrics snapshot comes
    # FIRST of the appended blocks; errors + null_sections + headline
    # close the artifact so a 2000-char tail cut cannot lose the
    # starvation evidence or the headline
    assert keys[-5:] == ["metrics", "regression", "errors",
                         "null_sections", "headline"]
    assert isinstance(out["metrics"], dict)
    assert out["null_sections"]["dtype_matrix_like"][
        "null_reason"].startswith("skipped")
    assert out["dtype_matrix_like"]["null_reason"].startswith("skipped")
    # the on-disk trajectory ends at r5, whose artifact predates several
    # watched keys — the verdict must exist either way, and its ok flag
    # is mirrored into the tail-surviving headline block
    assert out["headline"]["regression_ok"] == (
        out["regression"].get("ok")
        if isinstance(out["regression"], dict) else None
    )


def test_failed_ratio_sections_surface_as_starved_not_improvement():
    """A failed tuned_loop leaves vs_tuned_loop null in the headline
    (bench emits None instead of a /1e-9 garbage ratio); the sentinel
    must hard-fail it with the section's reason — not read a 1e9+
    'improvement' and exit 0."""
    cand = dict(HEADLINE)
    cand["vs_tuned_loop"] = None
    cand["repeat_mode_mpix"] = None
    v = regress.diff_headlines(
        _art(HEADLINE),
        _art(cand, errors={
            "tuned_loop": "RuntimeError: link died",
            "repeat_mode": "skipped: budget spent",
        }),
    )
    assert v["exit_code"] == 3
    reasons = {f["key"]: f["reason"] for f in v["findings"]}
    assert "link died" in reasons["vs_tuned_loop"]
    assert "budget spent" in reasons["repeat_mode_mpix"]


def test_critical_failure_artifact_still_finalized():
    """The early-exit path (headline measurement died) must still ship
    a finalized artifact: headline block present with a null
    mandelbrot_mpix, metrics + null_sections embedded, and the sentinel
    reports the framework section's reason."""
    bench = _bench()
    s = bench.SectionScheduler(100.0, {})
    full = s.run("framework", lambda: (_ for _ in ()).throw(
        RuntimeError("link died")), default=None, critical=True)
    assert full is None
    result = {
        "metric": "mandelbrot_throughput", "value": 0.0,
        "unit": "Mpixels/sec", "vs_baseline": 0.0, "errors": s.errors,
        "headline": {"mandelbrot_mpix": None, "n_errors": len(s.errors)},
    }
    bench.finalize_result(result, s)
    assert list(result)[-1] == "headline"
    assert list(result)[-2] == "null_sections"
    assert isinstance(result["metrics"], dict)
    assert result["null_sections"]["framework"]["null_reason"].startswith(
        "RuntimeError")
    # the EMBEDDED verdict (diffed against the on-disk trajectory, where
    # r5 carries mandelbrot_mpix) reads the same null_sections source as
    # the standalone CLI: reason arrives with budget_spent_s attached
    emb = result["regression"]
    if isinstance(emb, dict) and emb.get("findings"):
        by_key = {f["key"]: f for f in emb["findings"]}
        if "mandelbrot_mpix" in by_key:
            assert "budget_spent_s=" in by_key["mandelbrot_mpix"]["reason"]
    v = regress.diff_headlines(
        _art(HEADLINE),
        {"path": "<mem>", "headline": result["headline"],
         "errors": result["errors"],
         "null_sections": result["null_sections"], "sections": result},
    )
    assert v["exit_code"] == 3
    by_key = {f["key"]: f for f in v["findings"]}
    assert "link died" in by_key["mandelbrot_mpix"]["reason"]


def test_annotate_nulls_replaces_bare_nulls_only():
    bench = _bench()
    clock = _Clock()
    s = bench.SectionScheduler(
        100.0, {"dtype_matrix": 60.0, "marker_overhead": 10.0}, clock=clock)
    clock.t = 90.0
    dt = s.run("dtype_sweepish", lambda: None, default=None)
    nb = s.run("nbody", lambda: {"gpairs_per_sec": 0.0},
               default={"gpairs_per_sec": 0.0})
    result = {"dtype_sweepish": dt, "nbody": nb, "untouched": None}
    s.annotate_nulls(result)
    assert result["dtype_sweepish"]["null_reason"].startswith("skipped")
    assert result["dtype_sweepish"]["budget_spent_s"] == 90.0
    assert result["nbody"] == {"gpairs_per_sec": 0.0}  # real value kept
    assert result["untouched"] is None  # not a recorded section


# ---------------------------------------------------------------------------
# --history: the per-key trajectory table
# ---------------------------------------------------------------------------

def _write_round(root, n, headline):
    path = os.path.join(root, f"BENCH_r{n:02d}.json")
    with open(path, "w") as f:
        json.dump({"headline": headline}, f)
    return path


def test_history_table_values_cv_and_tolerance(tmp_path):
    root = str(tmp_path)
    # three rounds so the CV column engages for a stable key; one key
    # goes null in the last round and must render as null, not vanish
    for n, mpix in ((1, 240.0), (2, 250.0), (3, 245.0)):
        h = dict(HEADLINE)
        h["mandelbrot_mpix"] = mpix
        if n == 3:
            h["vs_tuned_loop"] = None
        _write_round(root, n, h)
    table = regress.history_table(root)
    lines = table.splitlines()
    assert lines[0].split()[:1] == ["key"]
    assert "r01" in lines[0] and "r03" in lines[0]
    assert "CV" in lines[0] and "tol" in lines[0]
    mandel = next(ln for ln in lines if ln.startswith("mandelbrot_mpix"))
    assert "240" in mandel and "250" in mandel and "245" in mandel
    tuned = next(ln for ln in lines if ln.startswith("vs_tuned_loop"))
    assert "null" in tuned
    # stable trajectory: CV small, tolerance stays at the floor (0.10)
    cv, tol = mandel.split()[-2:]
    assert float(cv) < 0.05 and float(tol) == 0.1


def test_history_table_empty_root(tmp_path):
    assert "no BENCH_r*.json" in regress.history_table(str(tmp_path))


def test_history_table_renders_missing_rounds_as_gaps(tmp_path):
    """r03/r04 absent between r02 and r05 → gap columns with `-`
    cells, DISTINCT from `null` (the round ran but starved the key)."""
    root = str(tmp_path)
    for n, mpix in ((1, 240.0), (2, 250.0), (5, None)):
        _write_round(root, n, {"mandelbrot_mpix": mpix,
                               "vs_tuned_loop": 1.0})
    table = regress.history_table(root)
    header = table.splitlines()[0]
    for col in ("r01", "r02", "r03", "r04", "r05"):
        assert col in header, table
    mandel = next(ln for ln in table.splitlines()
                  if ln.startswith("mandelbrot_mpix"))
    cells = mandel.split()
    # key, r01, r02, gap, gap, null, CV, tol
    assert cells[1:6] == ["240", "250", "-", "-", "null"], table


def test_cli_empty_trajectory_is_actionable_single_line(tmp_path):
    """(ISSUE 8 satellite) No parseable artifact → ONE actionable line
    on stderr and exit 1, never a traceback — for both the gating flow
    and --history."""
    root = str(tmp_path)
    # a binary/corrupt artifact: the shape that used to traceback
    # (UnicodeDecodeError inside load_headline)
    with open(os.path.join(root, "BENCH_r01.json"), "wb") as f:
        f.write(b"\x80\x81\xffnot json")

    def run(*args):
        return subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "regress.py"),
             "--root", root, *args],
            capture_output=True, text=True,
        )

    r = run("--against", os.path.join(root, "BENCH_r01.json"))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "Traceback" not in r.stderr and "Traceback" not in r.stdout
    assert "parses to a headline" in r.stderr
    assert len([ln for ln in r.stderr.splitlines() if ln.strip()]) == 1

    h = run("--history")
    assert h.returncode == 0, h.stdout + h.stderr
    assert "Traceback" not in h.stderr
    assert "parses to a headline" in h.stdout

    # a genuinely EMPTY root names the bootstrap action
    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    r2 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "regress.py"),
         "--root", empty, "--against", "BENCH_r99.json"],
        capture_output=True, text=True,
    )
    assert r2.returncode == 1
    assert "no BENCH_r*.json artifacts" in r2.stderr
    assert "bench.py" in r2.stderr and "Traceback" not in r2.stderr


def test_cli_explicit_candidate_bypasses_trajectory_check(tmp_path):
    """--candidate is an explicit pair diff: it must keep working even
    when the ROOT trajectory is empty/corrupt."""
    root = str(tmp_path)
    base = tmp_path / "base.json"
    cand = tmp_path / "cand.json"
    base.write_text(json.dumps({"headline": dict(HEADLINE)}))
    cand.write_text(json.dumps({"headline": dict(HEADLINE)}))
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "regress.py"),
         "--root", root, "--against", str(base), "--candidate", str(cand)],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stdout + r.stderr


def test_main_history_flag_short_circuits(tmp_path, capsys):
    _write_round(str(tmp_path), 1, HEADLINE)
    rc = regress.main(["--history", "--root", str(tmp_path)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "mandelbrot_mpix" in out and "tol" in out
