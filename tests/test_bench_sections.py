"""bench.py's SectionScheduler: the starvation-proofing contract
(VERDICT r5 #1 — dtype_matrix/marker_overhead shipped null two rounds
running because one global budget had no reservations).  Pure host
logic, driven with a fake clock."""

import bench


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_reserved_sections_run_after_budget_exhausted():
    clock = _Clock()
    s = bench.SectionScheduler(100.0, {"dtype_matrix": 30.0}, clock=clock)
    clock.t = 500.0  # way past budget
    assert s.run("dtype_matrix", lambda: "ran") == "ran"
    assert "dtype_matrix" not in s.errors


def test_nonreserved_section_skips_when_only_reserve_remains():
    clock = _Clock()
    s = bench.SectionScheduler(
        100.0, {"dtype_matrix": 30.0, "marker_overhead": 10.0}, clock=clock)
    clock.t = 65.0  # 35s left < 40s reserved -> non-reserved must skip
    assert s.run("expensive_middle", lambda: "ran", default=None) is None
    assert "reserved" in s.errors["expensive_middle"]
    # the reserved sections still run afterwards
    assert s.run("marker_overhead", lambda: "m") == "m"
    assert s.run("dtype_matrix", lambda: "d") == "d"


def test_nonreserved_section_runs_inside_budget():
    clock = _Clock()
    s = bench.SectionScheduler(100.0, {"dtype_matrix": 30.0}, clock=clock)
    clock.t = 50.0  # 50s left > 30s reserved
    assert s.run("mid", lambda: 42) == 42
    assert s.errors == {}


def test_critical_sections_always_run():
    clock = _Clock()
    s = bench.SectionScheduler(100.0, {"dtype_matrix": 30.0}, clock=clock)
    clock.t = 500.0
    assert s.run("framework", lambda: 1, critical=True) == 1


def test_section_exception_recorded_not_raised():
    s = bench.SectionScheduler(100.0, {})

    def boom():
        raise RuntimeError("link died")

    assert s.run("overlap", boom, default="dflt") == "dflt"
    assert s.errors["overlap"].startswith("RuntimeError")


def test_reserved_sections_registered_in_bench():
    # the two verdict-ordered sections AND the r6/r8 acceptance-gate
    # metrics must stay must-run
    assert "dtype_matrix" in bench.RESERVED_SECTIONS
    assert "marker_overhead" in bench.RESERVED_SECTIONS
    assert "flash_train" in bench.RESERVED_SECTIONS
    assert "dispatch_floor" in bench.RESERVED_SECTIONS


def test_small_budget_override_still_runs_best_effort_sections():
    # CK_BENCH_BUDGET_SEC below the reservation sum must not skip
    # everything from t=0 — reservations cap at 60% of the budget
    clock = _Clock()
    s = bench.SectionScheduler(600.0, dict(bench.RESERVED_SECTIONS),
                               clock=clock)
    assert s.run("baseline", lambda: "ran") == "ran"
    clock.t = 500.0  # past the capped 60% window -> best-effort skips
    assert s.run("overlap", lambda: "ran", default=None) is None


# ---------------------------------------------------------------------------
# fairness rotation (ISSUE 5 satellite): no section starves > 2 rounds
# ---------------------------------------------------------------------------

def test_rotation_promotes_two_round_starved_section():
    clock = _Clock()
    s = bench.SectionScheduler(
        100.0, {}, clock=clock,
        starvation_history=[{"marker_overhead"}, {"marker_overhead"}])
    assert s.rotation["promoted"] == ["marker_overhead"]
    assert s.rotation["starved_streak"] == ["marker_overhead"]
    assert s.reserved["marker_overhead"] == bench.FAIRNESS_SLICE_SEC
    # the promotion is a REAL must-run slice: it runs past budget
    clock.t = 500.0
    assert s.run("marker_overhead", lambda: "ran") == "ran"
    assert "marker_overhead" not in s.errors


def test_rotation_needs_two_consecutive_rounds():
    for hist in ([], [{"a"}], [{"a"}, {"b"}], [{"a"}, set(), {"a"}]):
        s = bench.SectionScheduler(100.0, {}, starvation_history=hist)
        assert s.rotation["promoted"] is None, hist
        assert s.rotation["starved_streak"] == []


def test_rotation_promotes_whole_multi_member_streak():
    """EVERY member of a multi-member streak is promoted the same round
    — a one-per-round rotation would leave a k-member streak's last
    member starving k+1 consecutive rounds, breaking the 'no section
    starves more than 2 consecutive rounds' guarantee for the
    motivating case itself (marker_overhead AND dtype_matrix starved
    together).  The rotation anchor only orders the list."""
    h2 = [{"a", "b"}, {"a", "b"}]
    s2 = bench.SectionScheduler(100.0, {}, starvation_history=h2)
    s3 = bench.SectionScheduler(100.0, {}, starvation_history=h2 + [{"a", "b"}])
    assert set(s2.rotation["promoted"]) == {"a", "b"}
    assert set(s3.rotation["promoted"]) == {"a", "b"}
    assert s2.reserved["a"] == s2.reserved["b"] == bench.FAIRNESS_SLICE_SEC
    # the anchor rotates with round count; same trajectory, same order
    assert s2.rotation["promoted"] != s3.rotation["promoted"]
    again = bench.SectionScheduler(100.0, {}, starvation_history=h2)
    assert again.rotation["promoted"] == s2.rotation["promoted"]


def test_rotation_never_shrinks_an_explicit_reservation():
    s = bench.SectionScheduler(
        1000.0, {"dtype_matrix": 430.0}, 
        starvation_history=[{"dtype_matrix"}, {"dtype_matrix"}])
    assert s.reserved["dtype_matrix"] == 430.0


def test_rotation_decision_lands_in_artifact():
    s = bench.SectionScheduler(
        100.0, {}, starvation_history=[{"ov"}, {"ov"}])
    result = {"headline": {}}
    bench.finalize_result(result, s)
    rot = result["scheduler_rotation"]
    assert rot["promoted"] == ["ov"]
    assert rot["slice_s"] == bench.FAIRNESS_SLICE_SEC
    assert rot["rounds_seen"] == 2


def test_starvation_history_reads_budget_skips_only(tmp_path):
    """History counts BUDGET starvation, not crashes: a must-run slice
    cannot fix a RuntimeError, so error nulls stay out of the streak."""
    import json

    for r in (1, 2):
        (tmp_path / f"BENCH_r0{r}.json").write_text(json.dumps({
            "null_sections": {
                "ov": {"null_reason": "skipped: 1500s bench budget spent",
                        "budget_spent_s": 1430.0},
                "boom": {"null_reason": "RuntimeError: link died",
                          "budget_spent_s": 100.0},
            },
            "headline": {"mandelbrot_mpix": 1.0},
        }))
    hist = bench.starvation_history(str(tmp_path))
    assert hist == [{"ov"}, {"ov"}]
    s = bench.SectionScheduler(100.0, {}, starvation_history=hist)
    assert s.rotation["promoted"] == ["ov"]
