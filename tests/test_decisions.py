"""Decision provenance (obs/decisions.py + obs/replay.py +
tools/ckreplay.py): the event-sourced controller decision log, its
replay-verify / what-if / explain consumers, the golden-log fixtures,
and the live integration (workload -> spill -> `ckreplay verify` exit 0,
`/decisionz`, postmortem v2).

Budget discipline mirrors tests/test_obs.py: the decision log is an
always-on family, so its disabled cost is pinned to the PR 4 budget
(< 100 ns marginal over the bare method-call floor), and a FULL ring
must never block an append (maxlen eviction, no lock)."""

import importlib.util
import json
import os
import threading
import time
import urllib.request
from functools import partial

import numpy as np
import pytest

from cekirdekler_tpu import ClArray
from cekirdekler_tpu.core import NumberCruncher
from cekirdekler_tpu.core import balance as balance_mod
from cekirdekler_tpu.core.balance import (
    BalanceHistory,
    BalanceState,
    equal_split,
    load_balance,
)
from cekirdekler_tpu.core.stream import TransferTuner
from cekirdekler_tpu.hardware import platforms
from cekirdekler_tpu.obs import replay as replay_mod
from cekirdekler_tpu.obs.decisions import (
    DECISION_KINDS,
    DECISIONS,
    REPLAYABLE_KINDS,
    DecisionLog,
    DecisionRecord,
    load_decision_log,
)
from cekirdekler_tpu.obs.flight import dump_postmortem, load_postmortem
from cekirdekler_tpu.obs.health import HealthMonitor, evaluate_window
from cekirdekler_tpu.utils.jsonsafe import json_safe

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
GOLDEN = os.path.join(HERE, "fixtures_decisions", "golden_rebalance.jsonl")
GOLDEN_HETERO = os.path.join(
    HERE, "fixtures_decisions", "golden_hetero_prior.jsonl")

INC = """
__kernel void inc(__global float* a) {
    int i = get_global_id(0);
    a[i] = a[i] + 1.0f;
}
"""


def _load_tool(name, relpath):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ckreplay = _load_tool("ck_replay_tool", "tools/ckreplay.py")


@pytest.fixture(scope="module")
def devs():
    return platforms().cpus()


def _since(mark: int) -> list:
    """Records the global log gained after seq ``mark`` — the isolation
    idiom for a shared process-global ring."""
    return [r for r in DECISIONS.snapshot() if r.seq > mark]


def _mark() -> int:
    recs = DECISIONS.snapshot()
    return recs[-1].seq if recs else 0


def _write_jsonl(path, rows):
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(json_safe(r.to_row()), allow_nan=False)
                    + "\n")
    return str(path)


# ---------------------------------------------------------------------------
# ring semantics + the overhead/never-blocks budget
# ---------------------------------------------------------------------------

def test_ring_bounded_seq_monotone():
    log = DecisionLog(capacity=32)
    for i in range(100):
        log.record("load-balance", {"i": i}, {})
    recs = log.snapshot()
    assert len(recs) == 32
    assert log.total_recorded == 100
    seqs = [r.seq for r in recs]
    assert seqs == sorted(seqs) and len(set(seqs)) == 32
    assert recs[-1].inputs["i"] == 99
    log.clear()
    assert log.snapshot() == [] and log.total_recorded == 0


class _NoopShape:
    """Same call shape as DecisionLog.record with the body removed —
    the interpreter's bound-method floor."""

    def record(self, kind, inputs=None, outputs=None):
        pass


def _best_pair(fn_floor, fn_probe, n=100_000, trials=10):
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


def test_disabled_record_overhead_under_budget():
    """The PR 4 pin applied to the new always-on family: disabled
    record() costs < 100 ns marginal over the identical no-op call."""
    log = DecisionLog()
    log.enabled = False
    noop = _NoopShape()
    floor, per = _best_pair(
        partial(noop.record, "probe"), partial(log.record, "probe"))
    net = per - floor
    assert net < 100e-9, (
        f"disabled record adds {net*1e9:.0f} ns over the call floor "
        f"({per*1e9:.0f} ns total, floor {floor*1e9:.0f} ns)")
    assert per < 1e-6
    assert log.total_recorded == 0


def test_full_ring_never_blocks_appends():
    """Property: appending to a FULL ring is eviction, not blocking —
    4 concurrent writers push 20k records each through a 64-slot ring
    with unique strictly-orderable seqs and no deadlock/timeout."""
    log = DecisionLog(capacity=64)
    for i in range(64):
        log.record("load-balance", {"warm": i}, {})
    assert len(log.snapshot()) == 64  # full from here on
    errs: list = []

    def writer():
        try:
            for i in range(20_000):
                log.record("transfer-choose", {"i": i}, {})
        except Exception as e:  # noqa: BLE001 - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=writer) for _ in range(4)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errs, errs
    assert all(not t.is_alive() for t in threads), "an append blocked"
    assert time.perf_counter() - t0 < 30.0
    recs = log.snapshot()
    assert len(recs) == 64
    seqs = [r.seq for r in recs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


# ---------------------------------------------------------------------------
# jsonl spill: save/load round trip, tmp+rename arming, throttle
# ---------------------------------------------------------------------------

def test_jsonl_round_trip_and_torn_tail(tmp_path):
    log = DecisionLog()
    log.record("load-balance", {"benchmarks": [1.5, 2.5]}, {"ranges": [64]})
    log.record("transfer-choose", {"kernel_key": ["inc", []]}, {"chunks": 4})
    p = str(tmp_path / "log.jsonl")
    log.save_jsonl(p)
    back = load_decision_log(p)
    assert [r.to_row() for r in back] == \
        [r.to_row() for r in log.snapshot()]
    # torn tail: a dying process's half-written last line is skipped
    with open(p, "a") as f:
        f.write('{"seq": 999, "kind": "load-bal')
    assert [r.seq for r in load_decision_log(p)] == \
        [r.seq for r in back]


def test_env_spill_is_armed_throttled_and_atomic(tmp_path, monkeypatch):
    p = str(tmp_path / "spill.jsonl")
    log = DecisionLog(spill_interval_s=3600.0)
    # unarmed: nothing touches disk
    log.record("load-balance", {}, {})
    assert log.maybe_spill() is None and not os.path.exists(p)
    # review finding: a SET-BUT-EMPTY env var is "off" under ONE
    # truthiness rule — the buffer must not accumulate rows no spill
    # site would ever write
    monkeypatch.setenv("CK_DECISION_LOG", "")
    log.record("load-balance", {}, {})
    assert len(log._spill) == 0 and log.maybe_spill() is None
    monkeypatch.setenv("CK_DECISION_LOG", p)
    log.record("load-balance", {"a": 1}, {"ranges": [8]})
    assert log.maybe_spill() == p  # first spill goes through
    assert log.maybe_spill() is None  # throttled inside the interval
    assert log.maybe_spill(force=True) == p  # dispose path
    assert not os.path.exists(p + ".tmp")  # tmp+rename left no turd
    rows = load_decision_log(p)
    assert rows and rows[-1].outputs == {"ranges": [8]}
    with open(p) as f:
        header = json.loads(f.readline())
    assert header["schema"] == "ck-decision-log-v1"


def test_armed_spills_append_incrementally_and_keep_evicted_rows(
        tmp_path, monkeypatch):
    """Review finding: a sync-point spill must cost O(new rows), not a
    rewrite of the whole history — later spills APPEND past the
    persisted watermark, and rows the bounded buffer later evicts stay
    on disk (the file is a SUPERSET of the buffer)."""
    p = str(tmp_path / "incr.jsonl")
    monkeypatch.setenv("CK_DECISION_LOG", p)
    log = DecisionLog()
    log.record("load-balance", {"i": 0}, {})
    assert log.spill() == p
    size1 = os.path.getsize(p)
    for i in range(1, 4):
        log.record("load-balance", {"i": i}, {})
    log.spill()
    # appended, not rewritten: the original bytes are a prefix
    assert os.path.getsize(p) > size1
    with open(p) as f:
        assert json.loads(f.readline())["schema"] == "ck-decision-log-v1"
    rows = load_decision_log(p)
    assert [r.inputs["i"] for r in rows] == [0, 1, 2, 3]
    # no duplicate seqs across spill boundaries
    assert len({r.seq for r in rows}) == len(rows)
    # eviction (buffer wraps) cannot lose already-persisted rows
    log2 = DecisionLog()
    p2 = str(tmp_path / "evict.jsonl")
    monkeypatch.setenv("CK_DECISION_LOG", p2)
    log2._spill = type(log2._spill)(maxlen=2)
    log2.record("load-balance", {"i": 0}, {})
    log2.spill()
    for i in range(1, 5):
        log2.record("load-balance", {"i": i}, {})
    log2.spill()  # buffer holds only i=3,4 now; file kept i=0
    kept = [r.inputs["i"] for r in load_decision_log(p2)]
    assert kept[0] == 0 and kept[-1] == 4


def test_spill_path_directory_is_per_process(tmp_path, monkeypatch):
    """Review finding: N processes sharing one armed env (a DCN job,
    a launcher's CPU-pinned child) must not last-writer-win one file — a
    directory value resolves to ck_decisions_<pid>.jsonl inside it."""
    d = str(tmp_path / "logs")
    os.makedirs(d)
    monkeypatch.setenv("CK_DECISION_LOG", d)
    log = DecisionLog()
    resolved = log.spill_path()
    assert resolved == os.path.join(
        d, f"ck_decisions_{os.getpid()}.jsonl")
    log.record("load-balance", {}, {})
    assert log.spill() == resolved and os.path.exists(resolved)


# ---------------------------------------------------------------------------
# load_balance emission: complete inputs, actions, floor binding
# ---------------------------------------------------------------------------

def _run_chain(steps=10, jump=True, cid=0,
               rates=(0.0010, 0.0040, 0.0008),
               t_rates=(0.0002, 0.0002, 0.0030),
               total=8192, step=64):
    """The demo generator's synthetic convergence, inline (unequal
    lanes; lane 2's link wall 3x its compute — the floor binds)."""
    n = len(rates)
    ranges = equal_split(total, n, step)
    hist = BalanceHistory(weighted=True)
    state = BalanceState()
    for _ in range(steps):
        bench = [rates[i] * max(ranges[i], step) for i in range(n)]
        transfer = [t_rates[i] * max(ranges[i], step) for i in range(n)]
        ranges = load_balance(bench, ranges, total, step, hist,
                              state=state, transfer_ms=transfer,
                              jump_start=jump, cid=cid)
    return ranges


def test_load_balance_records_complete_inputs_and_actions():
    mark = _mark()
    _run_chain(steps=10, jump=True, cid=901)
    recs = [r for r in _since(mark) if r.kind == "load-balance"
            and r.inputs.get("cid") == 901]
    assert len(recs) == 10
    inp = recs[0].inputs
    for key in ("benchmarks", "ranges", "total", "step", "damping",
                "transfer_ms", "jump_start", "cid", "history", "carry",
                "state"):
        assert key in inp, key
    assert inp["state"] == {"cont": [], "prev_delta": [], "damp": [],
                            "jumped": False, "warm": False}
    actions = [r.outputs["action"] for r in recs]
    # first measured rebalance arms (damped), second jumps, the
    # converged tail freezes
    assert actions[0] == "damped" and recs[0].outputs["jump_armed"]
    assert actions[1] == "jump"
    assert "freeze" in actions[2:]
    # the transfer floor BINDS on lane 2 (link 3x compute) and is
    # recorded as such, with the effective time equal to the floor
    jumped = recs[1]
    assert jumped.outputs["floor_bound"][2] is True
    assert jumped.outputs["effective_ms"][2] == \
        pytest.approx(jumped.inputs["transfer_ms"][2])
    # freeze records carry the quantization-floor evidence
    fz = next(r for r in recs if r.outputs["action"] == "freeze")
    assert fz.outputs["freeze"]["one_step_work_ms"] > 0
    assert fz.outputs["ranges"] == fz.inputs["ranges"]


# ---------------------------------------------------------------------------
# replay-verify: golden fixture, perturbed knobs, tampered outputs
# ---------------------------------------------------------------------------

def test_golden_fixture_replays_bit_identically():
    """The checked-in multi-lane rebalance log (a jump-start chain AND
    a damped chain, with a transfer-floor-bound lane) re-executes
    bit-identically — recorded logs ARE golden tests of the
    balancer."""
    rows = load_decision_log(GOLDEN)
    assert len(rows) >= 20
    assert any(r.outputs.get("action") == "jump" for r in rows)
    assert any(any(r.outputs.get("floor_bound") or [])
               for r in rows)
    verdict = replay_mod.verify_records(rows)
    assert verdict["ok"], verdict["first_divergence"]
    assert verdict["replayed"] == len(rows)
    assert verdict["first_divergence"] is None


def test_golden_hetero_prior_fixture_replays_and_whatif_contrast():
    """ISSUE 20 golden fixture: a prior-seeded heterogeneous chain (one
    fast + one 100x-slow lane) replays bit-identically, the chain is
    genuinely seeded FROM the prior-split record, and the what-if
    counterfactual quantifies the prior's win — prior-on converges in
    at most HALF the iterations of prior-off on the same recorded
    rates (the acceptance bar: the seed starts the chain already at
    the rate-implied split, so the damped iteration has nothing left
    to move)."""
    rows = load_decision_log(GOLDEN_HETERO)
    assert any(r.kind == "prior-split" for r in rows)
    verdict = replay_mod.verify_records(rows)
    assert verdict["ok"], verdict["first_divergence"]
    assert verdict["replayed"] == len(rows)
    # the first balance step starts FROM the prior-split output
    seed = next(r for r in rows if r.kind == "prior-split")
    first_lb = next(r for r in rows if r.kind == "load-balance")
    assert first_lb.inputs["ranges"] == seed.outputs["ranges"]
    assert first_lb.inputs["rate_prior"] == seed.inputs["priors"]
    # counterfactual: filing the prior off restarts from equal_split
    wi = replay_mod.whatif(rows, {"rate_prior": False})
    on = wi["factual"]["iterations_to_converge"]
    off = wi["counterfactual"]["iterations_to_converge"]
    assert wi["factual"]["converged"] and wi["counterfactual"]["converged"]
    assert off >= 1, "prior-off control never had to move?"
    assert on <= off / 2, (on, off)
    # both land on the SAME split — the prior buys convergence speed,
    # never a different answer
    assert wi["final_split_l1"] == 0


def test_perturbed_knob_fails_naming_first_divergent_seq(monkeypatch):
    """The acceptance contract: someone edits a balancer knob — replay
    of an old log must fail and NAME the first divergent seq."""
    rows = load_decision_log(GOLDEN)
    monkeypatch.setattr(balance_mod, "FREEZE_MARGIN", 0.3)
    verdict = replay_mod.verify_records(rows)
    assert not verdict["ok"]
    first = verdict["first_divergence"]
    assert first is not None and isinstance(first["seq"], int)
    assert first["kind"] == "load-balance"
    # it is genuinely the FIRST divergent record
    assert first["seq"] == min(d["seq"] for d in verdict["divergences"])
    # a second, orthogonal knob class: the adaptive-damping ceiling
    monkeypatch.setattr(balance_mod, "FREEZE_MARGIN", 0.6)
    monkeypatch.setattr(balance_mod, "DAMP_MAX_SMOOTHED", 0.5)
    v2 = replay_mod.verify_records(rows)
    assert not v2["ok"] and v2["first_divergence"]["seq"] > 0


def test_ckreplay_cli_verify_exit_codes(capsys, monkeypatch):
    assert ckreplay.main(["verify", GOLDEN]) == 0
    out = capsys.readouterr().out
    assert "OK" in out and "bit-identically" in out
    monkeypatch.setattr(balance_mod, "FREEZE_MARGIN", 0.3)
    assert ckreplay.main(["verify", GOLDEN]) == 1
    out = capsys.readouterr().out
    assert "first divergent seq=" in out


def test_tampered_outputs_are_divergence():
    rows = [r.to_row() for r in load_decision_log(GOLDEN)]
    tampered = json.loads(json.dumps(rows))
    victim = next(r for r in tampered if r["kind"] == "load-balance")
    victim["outputs"]["ranges"][0] += victim["inputs"]["step"]
    victim["outputs"]["ranges"][1] -= victim["inputs"]["step"]
    verdict = replay_mod.verify_records(tampered)
    assert not verdict["ok"]
    assert verdict["first_divergence"]["seq"] == victim["seq"]
    assert "ranges" in verdict["first_divergence"]["mismatch"]


def test_replay_does_not_rerecord(monkeypatch):
    rows = load_decision_log(GOLDEN)
    mark = _mark()
    assert replay_mod.verify_records(rows)["ok"]
    assert _since(mark) == [], "replay re-recorded into the live log"
    assert DECISIONS.enabled, "quiesce failed to restore"


def test_overlapping_replays_restore_only_at_outermost_exit():
    """Review finding: two concurrent replays share the process-global
    quiesce — the first to finish must NOT re-enable recording while
    the second is still re-executing (its replayed calls would land in
    the live ring as echoes)."""
    rows = load_decision_log(GOLDEN)
    mark = _mark()
    errs: list = []

    def worker():
        try:
            for _ in range(5):
                assert replay_mod.verify_records(rows)["ok"]
        except Exception as e:  # noqa: BLE001 - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs, errs
    assert DECISIONS.enabled, "outermost restore lost"
    assert _since(mark) == [], "a replay echo leaked into the live log"


def test_divergence_counts_cover_the_whole_log(monkeypatch):
    """Review finding: the divergence-DETAIL cap must not truncate the
    scan — a fully-divergent long log still reports replayed == every
    replayable record, with the overflow flagged."""
    rows = load_decision_log(GOLDEN)
    monkeypatch.setattr(balance_mod, "FREEZE_MARGIN", 0.3)
    v = replay_mod.verify_records(rows, max_divergences=2)
    assert v["replayed"] == len(rows)
    assert v["divergent"] > 2 and len(v["divergences"]) == 2
    assert v["divergences_truncated"] is True


# ---------------------------------------------------------------------------
# what-if: counterfactual chained runs
# ---------------------------------------------------------------------------

def test_whatif_jump_off_strictly_slower():
    """The acceptance pin: disabling jump-start on the jump-started
    recorded log brings back the damped crawl — strictly MORE
    iterations to converge than the factual run."""
    rows = load_decision_log(GOLDEN)
    rep = replay_mod.whatif(rows, {"jump_start": False}, cid=0)
    f, c = rep["factual"], rep["counterfactual"]
    assert f["converged"] and c["converged"]
    assert c["iterations_to_converge"] > f["iterations_to_converge"]


def test_whatif_factual_reproduces_recorded_trajectory():
    """The rate-model simulator run WITHOUT overrides must retrace the
    log exactly while the log lasts (the consistency anchor that makes
    the counterfactual comparison meaningful)."""
    rows = load_decision_log(GOLDEN)
    recs = [r.to_row() for r in rows
            if r.kind == "load-balance" and r.inputs.get("cid") == 0]
    sim = replay_mod.simulate_balance(recs, {})
    recorded = [list(r["outputs"]["ranges"]) for r in recs]
    assert sim["trajectory"][1:len(recs) + 1] == recorded


def test_whatif_transfer_floor_off_moves_the_split():
    """Lane 2's split share is floor-limited; removing the floor must
    hand it more items (its compute rate is the fastest)."""
    rows = load_decision_log(GOLDEN)
    rep = replay_mod.whatif(rows, {"transfer_floor": False}, cid=0)
    assert rep["final_split_l1"] > 0
    assert rep["counterfactual"]["final_ranges"][2] > \
        rep["factual"]["final_ranges"][2]


def test_whatif_unknown_knob_refused():
    rows = load_decision_log(GOLDEN)
    with pytest.raises(ValueError, match="unknown what-if knob"):
        replay_mod.whatif(rows, {"warp_speed": 9})
    with pytest.raises(SystemExit):
        ckreplay.parse_overrides("warp_speed=9")
    assert ckreplay.parse_overrides(
        "damping=0.1,jump_start=off,transfer_floor=on,overhead_ms=2") == {
        "damping": 0.1, "jump_start": False, "transfer_floor": True,
        "overhead_ms": 2.0}
    # review finding: coercion is typed PER KNOB — a float knob given
    # on/off must be rejected (not silently become 0.0), and a bool
    # knob given a number must not float-parse into truthy-on
    with pytest.raises(SystemExit):
        ckreplay.parse_overrides("overhead_ms=off")
    with pytest.raises(SystemExit):
        ckreplay.parse_overrides("damping=on")
    with pytest.raises(SystemExit):
        ckreplay.parse_overrides("jump_start=0.3")


# ---------------------------------------------------------------------------
# explain: the causality table
# ---------------------------------------------------------------------------

def test_explain_latest_causality_table():
    rows = load_decision_log(GOLDEN)
    doc = replay_mod.explain_latest(rows, cid=0)
    assert doc["action"] == "freeze" and "freeze" in doc
    assert len(doc["lanes"]) == 3
    lane2 = doc["lanes"][2]
    # the link-bound lane: floor margin positive (the floor BINDS),
    # effective time = the transfer wall, residue ~0 on a frozen split
    assert lane2["floor_bound"] is True
    assert lane2["floor_margin_ms"] > 0
    assert lane2["effective_ms"] == pytest.approx(lane2["transfer_ms"])
    assert doc["lanes"][0]["floor_margin_ms"] < 0  # slack lane
    # a DAMPED iteration names the per-lane binding input
    damped = next(r for r in rows if r.kind == "load-balance"
                  and r.outputs.get("action") == "damped"
                  and any(r.outputs.get("floor_bound") or []))
    d2 = replay_mod.explain_balance(damped)
    bindings = [ln["binding"] for ln in d2["lanes"]]
    assert "transfer floor (link-bound)" in bindings
    assert any(b.startswith("compute bench") for b in bindings)
    # the text renderer carries every lane row
    text = ckreplay.render_explain(d2)
    assert "binding" in text and "transfer floor" in text


def test_ckreplay_cli_explain_and_whatif(capsys):
    assert ckreplay.main(["explain", GOLDEN, "--cid", "0"]) == 0
    out = capsys.readouterr().out
    assert "action=freeze" in out and "quantization floor" in out
    assert ckreplay.main(
        ["whatif", GOLDEN, "--set", "jump_start=off", "--cid", "0"]) == 0
    out = capsys.readouterr().out
    assert "LATER" in out  # strictly-slower counterfactual, rendered


# ---------------------------------------------------------------------------
# transfer tuner decisions: choose + observe replay
# ---------------------------------------------------------------------------

def test_transfer_choose_records_and_replays():
    mark = _mark()
    t = TransferTuner()
    key = ("inc", ())
    # measuring run -> fenced observation -> model choice
    assert t.choose(0, key, 1 << 20, 16) == 1
    t.observe(0, key, 1 << 20, 4.0, 1.0, 4.0, chunks=1, fenced=True)
    c = t.choose(0, key, 1 << 20, 16)
    assert c > 1  # transfer-dominated: chunking wins
    # a no-compute key models straight from the duplex seed
    t.seed_link(1, 5.0, 5.0)
    c2 = t.choose(1, "flush-d2h", 1 << 22, 64, has_compute=False)
    assert c2 > 1
    recs = _since(mark)
    chooses = [r for r in recs if r.kind == "transfer-choose"]
    observes = [r for r in recs if r.kind == "transfer-observe"]
    assert len(chooses) == 3 and len(observes) == 1
    whys = [r.outputs["why"] for r in chooses]
    assert whys == ["measuring-run", "model", "model"]
    assert chooses[2].inputs["seed"] == {
        "h2d_ms_per_mib": 5.0, "d2h_ms_per_mib": 5.0}
    verdict = replay_mod.verify_records(recs)
    assert verdict["ok"], verdict["first_divergence"]


def test_transfer_observe_replay_exact_ema_arithmetic():
    """The EMA/clamp/overhead update arithmetic replays to exact float
    equality from the recorded pre-state (fenced EMA, unfenced clamp,
    chunked overhead-learning — all three update classes)."""
    mark = _mark()
    t = TransferTuner()
    key = ("nbody", (("dt", 0.01),))
    t.observe(0, key, 1 << 21, 8.0, 2.0, 8.0, chunks=1, fenced=True)
    t.observe(0, key, 1 << 21, 7.0, 2.5, 6.0, chunks=1, fenced=True)  # EMA
    t.observe(0, key, 1 << 21, 0.0, 0.0, 5.0, chunks=1,
              wall_ms=5.0, fenced=False)                 # clamp-only
    t.observe(0, key, 1 << 21, 1.0, 0.5, 1.0, chunks=4,
              wall_ms=9.0)                               # overhead learn
    recs = [r for r in _since(mark) if r.kind == "transfer-observe"]
    assert len(recs) == 4
    assert recs[-1].outputs["overhead_ms"] != \
        recs[0].outputs["overhead_ms"]
    verdict = replay_mod.verify_records(recs)
    assert verdict["ok"], verdict["first_divergence"]
    # tamper one stored float by 1 ulp-scale nudge: exactness means it
    # MUST diverge
    rows = [r.to_row() for r in recs]
    rows[1] = json.loads(json.dumps(rows[1]))
    rows[1]["outputs"]["obs"]["u_ms"] += 1e-9
    assert not replay_mod.verify_records(rows)["ok"]


# ---------------------------------------------------------------------------
# health decisions: pure transition, flip records, drain advisory
# ---------------------------------------------------------------------------

def test_evaluate_window_pure_transitions():
    kw = dict(threshold=3.0, confirm=2, release=1.5)
    assert evaluate_window(1.0, None, streak=0, degraded=False, **kw) == {
        "flagged": False, "ratio": None, "streak": 0, "degraded": False}
    r = evaluate_window(9.0, 1.0, streak=1, degraded=False, **kw)
    assert r == {"flagged": True, "ratio": 9.0, "streak": 2,
                 "degraded": True}
    # hysteresis: above release stays degraded, at/below releases
    assert evaluate_window(2.0, 1.0, streak=2, degraded=True,
                           **kw)["degraded"] is True
    assert evaluate_window(1.4, 1.0, streak=2, degraded=True,
                           **kw)["degraded"] is False
    # zero baseline: material sample strikes, ratio stays JSON-safe
    z = evaluate_window(0.5, 0.0, streak=0, degraded=False, **kw)
    assert z["flagged"] and z["ratio"] is None


def test_health_flip_records_decision_and_replays():
    mark = _mark()
    hm = HealthMonitor(threshold=3.0, window=4, confirm=2, min_history=2)
    steady = [0.010] * hm.window
    for _ in range(hm.min_history + 1):
        for v in steady:
            hm.observe(0, "fence", v)
    for _ in range(hm.confirm):
        for v in [0.08] * hm.window:
            hm.observe(0, "fence", v)
    assert hm.verdict(0) == "degraded"
    flips = [r for r in _since(mark) if r.kind == "health-verdict"]
    # ok -> suspect -> degraded: two flips, with the full transition
    # inputs recorded
    assert [r.outputs["state"] for r in flips] == ["suspect", "degraded"]
    assert flips[0].inputs["signal"] == "fence"
    assert flips[0].inputs["baseline_s"] == pytest.approx(0.010)
    verdict = replay_mod.verify_records(flips)
    assert verdict["ok"], verdict["first_divergence"]
    # the advisory records provenance too
    assert hm.suggest_drain() == [0]
    adv = [r for r in _since(mark) if r.kind == "drain-advisory"]
    assert adv and adv[-1].outputs["drain"] == [0]
    assert adv[-1].inputs["lanes"]["0"]["verdict"] == "degraded"


# ---------------------------------------------------------------------------
# live integration: workload -> records -> spill -> verify exit 0,
# /decisionz, fused decisions
# ---------------------------------------------------------------------------

def test_live_workload_log_verifies_and_serves_decisionz(
        devs, tmp_path):
    """The acceptance drive: a live multi-lane enqueue workload records
    decisions; the spilled log replay-verifies to exit 0 through the
    real CLI, and /decisionz renders the causality table."""
    mark = _mark()
    cr = NumberCruncher(devs.subset(2), INC)
    srv = cr.serve_debug(port=0)
    n = 4096
    a = ClArray(np.zeros(n, np.float32), name="dec_a", partial_read=True)
    try:
        cr.enqueue_mode = True
        for _w in range(6):
            for _ in range(8):
                a.compute(cr, 901, "inc", n, 64)
            cr.barrier()
        cr.enqueue_mode = False
        recs = _since(mark)
        kinds = {r.kind for r in recs}
        assert "fused-engage" in kinds or "fused-disengage" in kinds
        assert "load-balance" in kinds  # barriers armed rebalances
        lb = [r for r in recs if r.kind == "load-balance"
              and r.inputs.get("cid") == 901]
        assert lb and len(lb[0].inputs["benchmarks"]) == 2
        # the spilled log round-trips through the REAL CLI: exit 0,
        # bit-identical
        p = _write_jsonl(tmp_path / "live.jsonl", recs)
        assert ckreplay.main(["verify", p]) == 0
        # /decisionz: counts, recent rows, and the live explain table
        with urllib.request.urlopen(
                srv.url + "/decisionz", timeout=10) as r:
            body = json.loads(r.read().decode())
        assert body["counts"].get("load-balance", 0) >= 1
        assert body["total_recorded"] >= len(recs)
        assert body["recent"], "no recent decisions served"
        ex = body["explain"].get("901")
        assert ex is not None and len(ex["lanes"]) == 2
        assert all("binding" in ln for ln in ex["lanes"])
        # explain over the same records matches the endpoint's cid view
        doc = replay_mod.explain_latest(recs, cid=901)
        assert doc["cid"] == 901
    finally:
        cr.dispose()
    assert float(a.host()[0]) == float(a.host()[-1]) > 0  # bit-exact


def test_decision_kinds_vocabulary_is_total(devs):
    """Every kind the built-ins emit is declared, and the replayable
    subset is a subset of the declared vocabulary."""
    assert set(REPLAYABLE_KINDS) <= set(DECISION_KINDS)
    emitted = {r.kind for r in DECISIONS.snapshot()}
    assert emitted <= set(DECISION_KINDS), emitted - set(DECISION_KINDS)


# ---------------------------------------------------------------------------
# postmortem v2: the decision ring rides the black box; v1 still loads
# ---------------------------------------------------------------------------

def test_postmortem_v2_carries_decisions_and_replays(tmp_path):
    if not DECISIONS.snapshot():
        load_balance([1.0, 2.0], [64, 64], 128, 64,
                     None, state=BalanceState(), cid=1)
    p = str(tmp_path / "pm.json")
    dump_postmortem(p, exc=RuntimeError("boom"))
    pm = load_postmortem(p)
    assert pm["schema"] == "ck-postmortem-v2"
    assert pm["decisions"], "v2 dump carries no decision ring"
    assert pm["decisions_capacity"] == DECISIONS.capacity
    # the black box replays directly through the CLI loader
    rows = ckreplay.load_records(p)
    assert rows and replay_mod.verify_records(rows)["replayed"] >= 1


def test_postmortem_v1_files_still_load(tmp_path):
    """Round-trip pin for the additive schema bump: a v1 file (no
    decisions key) loads with decisions == [] and untouched spans."""
    v1 = {
        "schema": "ck-postmortem-v1",
        "wrote_at": 1700000000.0,
        "exc": None,
        "events": [{"t": 1.0, "kind": "barrier"}],
        "spans": [{"kind": "launch", "t0": 0.0, "t1": 0.001,
                   "cid": 1, "lane": 0, "tag": "x"}],
        "metrics": {},
        "lanes": None,
        "versions": {},
    }
    p = str(tmp_path / "v1.json")
    with open(p, "w") as f:
        json.dump(v1, f)
    pm = load_postmortem(p)
    assert pm["decisions"] == []
    assert pm["spans"][0].kind == "launch"
    assert ckreplay.load_records(p) == []
