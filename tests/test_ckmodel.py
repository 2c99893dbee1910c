"""ckmodel — bounded exhaustive model checker: the acceptance suite.

Layers:

1. **The gate** — ``check_all()`` is clean on HEAD at tier-1 bounds,
   explores ≥ 10k canonical states across the four machines inside the
   tier-1 wall budget, and every declared invariant is exercised.
2. **Deliberately-broken fixture machines** — every invariant in every
   controller module's ``MODEL_INVARIANTS`` is refuted by at least one
   injected-broken transition/masker/planner, producing a minimal
   counterexample trace (the table is completeness-checked against the
   declared invariant ids).
3. **The counterexample→replay bridge** — broken-machine drain traces
   DIVERGE under ``verify_counterexample`` naming the first divergent
   seq (the regression drill); real-machine balance traces spill as
   ``ck-decision-log-v1`` jsonl that ``ckreplay verify`` replays green
   and ``ckreplay explain`` renders end-to-end.
4. **Violations fixed in this PR, pinned** — the balancer ±1-step swap
   limit cycle (two equal-rate lanes + one slow lane flipped the
   repair step forever; fixed by the REPAIR_TIE_BAND incumbent
   tie-break) via the committed trace
   ``tests/fixtures_decisions/model_swap_cycle.jsonl`` plus a live
   re-drive, and the coalescer rotation starvation (a G=4 all-present
   schedule starved one group 6 consecutive rounds under the old
   whole-list rotation; fixed by longest-starved-first promotion) via
   the concrete schedule + a randomized property sweep.
5. **CLI lifecycle** — clean-on-HEAD gate, ratchet refuses growth
   without ``--allow-grow``, stale entries name the burn commit
   (shared provenance header), ``--json`` schema pinned,
   ``--save-trace`` spills replayable jsonl.
6. **Purity lint** — the model-checked functions are clean on HEAD;
   clock/RNG/mutable-global reads in fixtures are flagged.
"""

import json
import os
import random
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cekirdekler_tpu.analysis import model as M  # noqa: E402
from cekirdekler_tpu.cluster import elastic as E  # noqa: E402
from cekirdekler_tpu.core import balance as B  # noqa: E402
from cekirdekler_tpu.obs import drain as D  # noqa: E402
from cekirdekler_tpu.obs.decisions import (  # noqa: E402
    CONTEXT_KINDS,
    DECISION_KINDS,
    REPLAYABLE_KINDS,
    load_decision_log,
)
from cekirdekler_tpu.obs.replay import (  # noqa: E402
    save_counterexample,
    verify_counterexample,
    verify_records,
)
from cekirdekler_tpu.core import blocktuner as BT  # noqa: E402
from cekirdekler_tpu.serve import admission as A  # noqa: E402
from cekirdekler_tpu.serve import coalescer as C  # noqa: E402
from cekirdekler_tpu.serve import fabric as F  # noqa: E402
from cekirdekler_tpu.serve import resilience as R  # noqa: E402

import tools.ckmodel.cli as ckmodel_cli  # noqa: E402
from tools.ckmodel import purity  # noqa: E402

SWAP_CYCLE_FIXTURE = os.path.join(
    HERE, "fixtures_decisions", "model_swap_cycle.jsonl")


# ---------------------------------------------------------------------------
# 1. the gate: clean on HEAD, >= 10k states, every invariant exercised
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def head_report():
    t0 = time.perf_counter()
    rep = M.check_all()
    rep["_wall_s"] = time.perf_counter() - t0
    return rep


def test_clean_on_head_at_tier1_bounds(head_report):
    assert head_report["ok"], [
        v.render() for v in head_report["violations"]]
    assert not head_report["violations"]


def test_states_explored_floor_and_wall(head_report):
    """The acceptance bar: >= 10k canonical states across the four
    machines, inside the tier-1 wall budget (< 10 s excluding the
    package import, with a wide margin on this container)."""
    assert head_report["states_explored"] >= 10_000
    assert set(head_report["machines"]) == set(M.MACHINE_NAMES)
    for name, r in head_report["machines"].items():
        assert r["states_explored"] > 0, name
        assert not r["truncated"], name
    assert head_report["_wall_s"] < 10.0


def test_every_declared_invariant_exercised(head_report):
    for name, r in head_report["machines"].items():
        for sub, doc in r["sub_machines"].items():
            for inv_id, row in doc["invariants"].items():
                assert row["exercised"], (name, sub, inv_id)


def test_quick_profile_is_subsecond_and_jsonable():
    t0 = time.perf_counter()
    doc = M.tier1_check(quick=True)
    assert time.perf_counter() - t0 < 2.0
    assert doc["ok"] is True
    assert doc["states_explored"] > 0
    json.dumps(doc, allow_nan=False)  # strict JSON


def test_machines_declare_exactly_their_checks():
    """The _REPLAYERS discipline: a machine whose implemented checks
    drift from the module's MODEL_INVARIANTS refuses to construct."""

    class Drifted(M.DrainMachine):
        checks = ("availability-floor",)  # subset

    with pytest.raises(AssertionError, match="MODEL_INVARIANTS"):
        Drifted(lanes=2)


# ---------------------------------------------------------------------------
# 2. deliberately-broken fixture machines, one per declared invariant
# ---------------------------------------------------------------------------

def _no_floor(verdicts, states, hold, streak, hb, cc, probe_grace=2):
    res = D.drain_transition(verdicts, states, hold, streak, hb, cc,
                             probe_grace=probe_grace)
    for lane, v in {str(k): v for k, v in verdicts.items()}.items():
        if v == "degraded" and res["states"].get(lane) == D.LANE_ACTIVE:
            res["states"][lane] = D.LANE_QUARANTINED
            res["hold"][lane] = hb
            res["drained"].append(lane)
    return res


def _leaky_masker(ranges, step, drained, probation):
    out = list(D.apply_quarantine(ranges, step, drained, probation))
    if drained or probation:
        out[-1] += step  # invented share
    return out


def _double_probe_masker(ranges, step, drained, probation):
    # conservation-preserving but the probe share is 2 steps
    return D.apply_quarantine(ranges, 2 * step, drained, probation)


def _silent_drain(verdicts, states, hold, streak, hb, cc, probe_grace=2):
    res = D.drain_transition(verdicts, states, hold, streak, hb, cc,
                             probe_grace=probe_grace)
    if res["drained"]:
        res = dict(res, drained=res["drained"][:-1])  # hide one
    return res


def _never_readmit(verdicts, states, hold, streak, hb, cc, probe_grace=2):
    res = D.drain_transition(verdicts, states, hold, streak, hb, cc,
                             probe_grace=probe_grace)
    if res["readmitted"]:
        states_out = dict(res["states"])
        streak_out = dict(res["clear_streak"])
        for lane in res["readmitted"]:
            states_out[lane] = D.LANE_PROBATION
            streak_out[lane] = 0
        res = dict(res, states=states_out, clear_streak=streak_out,
                   readmitted=[])
    return res


def _flappy(verdicts, states, hold, streak, hb, cc, probe_grace=2):
    """Re-quarantines a probation lane even on an ok verdict — the
    stale-verdict relapse loop PR 12's probe_grace exists to prevent,
    taken to its extreme (no readmission path survives)."""
    res = D.drain_transition(verdicts, states, hold, streak, hb, cc,
                             probe_grace=probe_grace)
    vmap = {str(k): v for k, v in verdicts.items()}
    pre = {str(k): v for k, v in states.items()}
    for lane, st in pre.items():
        if st == D.LANE_PROBATION and vmap.get(lane, "ok") == "ok":
            res["states"][lane] = D.LANE_QUARANTINED
            res["hold"][lane] = hb
            res["clear_streak"][lane] = 0
            res["drained"].append(lane)
            if lane in res["readmitted"]:
                res["readmitted"].remove(lane)
    return res


def _drain_machine(**kw):
    return M.DrainMachine(lanes=2, hold_barriers=1, confirm_clear=1,
                          probe_grace=1, **kw)


class _DoubleEpoch(E.Membership):
    def _transition(self, kind, member, step, total):
        out = super()._transition(kind, member, step, total)
        with self._mu:
            self.epoch += 1  # a skipped number between records
        return out


class _NoJoins(E.Membership):
    def sync(self, present, total=None):
        present = {k: v for k, v in present.items()
                   if k in self.members}
        return super().sync(present, total)


class _FlakyOrder(E.Membership):
    """Keeps the roster outcome and the leaves-before-joins phase
    order, but flips the order WITHIN each phase on alternate drives —
    the exact nondeterminism deterministic-order exists to refuse."""

    FLIP = [False]

    def sync(self, present, total=None):
        _FlakyOrder.FLIP[0] = not _FlakyOrder.FLIP[0]
        rev = _FlakyOrder.FLIP[0]
        with self._mu:
            current = dict(self.members)
        resized = sorted(m for m in present
                         if m in current and present[m] != current[m])
        out = []
        for m in sorted(set(current) - set(present), reverse=rev) \
                + resized:
            out.append(self.leave(m, total))
        for m in sorted(set(present) - set(current), reverse=rev) \
                + resized:
            out.append(self.join(m, present[m], total))
        return out


def _elastic_machine(**kw):
    return M.ElasticMachine(member_ids=("p0", "p2"), steps=(2, 3), **kw)


def _quota_off_by_one(**kw):
    if (not kw["kernel_unsafe"] and kw["healthy"]
            and kw["queue_depth"] < kw["max_queue_depth"]
            and kw["tenant_inflight"] == kw["quota"]):
        return {"admit": True, "reason": None, "retry_after_s": None}
    return A.admit_decision(**kw)


def _no_queue_gate(**kw):
    return A.admit_decision(**dict(kw, queue_depth=0))


def _wrong_order(**kw):
    dec = A.admit_decision(**kw)
    if not dec["admit"] and not kw["healthy"] \
            and kw["tenant_inflight"] >= kw["quota"]:
        return {"admit": False, "reason": A.REJECT_QUOTA,
                "retry_after_s": dec["retry_after_s"]}
    return dec


def _kernel_backoff(**kw):
    dec = A.admit_decision(**kw)
    if dec.get("reason") == A.REJECT_KERNEL:
        return dict(dec, retry_after_s=1.0)
    return dec


def _moody(**kw):
    dec = A.admit_decision(**kw)
    if dec["admit"] and kw["tenant_inflight"] == 1:
        return {"admit": False, "reason": A.REJECT_QUOTA,
                "retry_after_s": 0.1}
    return dec


def _admission_machine(**kw):
    return M.AdmissionMachine(tenants=("a", "b"), quota=2,
                              max_queue_depth=2, **kw)


def _overpromote(groups, rnd, mp):
    plan = C.plan_coalesce(groups, rnd, mp)
    keys = [str(g["key"]) for g in groups if int(g.get("pending", 0))]
    if keys and not plan["promoted"]:
        plan = dict(plan, promoted=[keys[0]])
    return plan


def _order_dropper(groups, rnd, mp):
    plan = C.plan_coalesce(groups, rnd, mp)
    if len(plan["order"]) > 1:
        order = plan["order"][:-1]
        plan = dict(plan, order=order,
                    picked=order[:mp] if mp > 0 else list(order))
    return plan


_jitter_seen: dict = {}


def _jitter(groups, rnd, mp):
    """Nondeterministic per SNAPSHOT: the first plan of a given
    snapshot is real, every replan of the same snapshot is tampered —
    exactly the replay-breaking drift plan-deterministic refuses."""
    plan = C.plan_coalesce(groups, rnd, mp)
    key = (rnd, tuple(sorted(
        (g["key"], g.get("starved_rounds", 0)) for g in groups)))
    n = _jitter_seen.get(key, 0)
    _jitter_seen[key] = n + 1
    if n > 0 and len(plan["order"]) > 1:
        order = list(plan["order"])
        order[0], order[-1] = order[-1], order[0]
        plan = dict(plan, order=order,
                    picked=order[:mp] if mp > 0 else list(order))
    return plan


def _no_fairness(groups, rnd, mp):
    """The pre-r10 strawman: EDF/age only, no promotion — the youngest
    group starves unboundedly behind fixed older/deadlined peers."""
    rows = [g for g in groups if int(g.get("pending", 0)) > 0]
    order = [str(g["key"]) for g in sorted(rows, key=C._edf_key)]
    picked = order[:mp] if mp > 0 else list(order)
    return {"order": order, "picked": picked, "promoted": [],
            "max_picks": mp if mp > 0 else 0}


def _coalesce_machine(**kw):
    return M.CoalesceMachine(keys=("ga", "gb", "gc"), max_picks=1, **kw)


def _lossy_balance(bench, ranges, total, step, hist, **kw):
    out = list(B.load_balance(bench, ranges, total, step, hist, **kw))
    if out[0] >= step:
        out[0] -= step
    return out


def _unquantized_balance(bench, ranges, total, step, hist, **kw):
    out = list(B.load_balance(bench, ranges, total, step, hist, **kw))
    if len(out) > 1:
        out[0] += 1
        out[-1] -= 1
    return out


def _rejump_balance(bench, ranges, total, step, hist, state=None, **kw):
    out = B.load_balance(bench, ranges, total, step, hist,
                         state=state, **kw)
    if state is not None and state.jumped:
        state.jumped = False  # the one-shot latch filed off
    return out


def _freeze_mover(bench, ranges, total, step, hist, **kw):
    src = list(ranges)
    out = list(B.load_balance(bench, ranges, total, step, hist, **kw))
    if out == src and len(out) > 1 and out[0] >= step:
        out[0] -= step
        out[1] += step
    return out


_osc_flip = [False]


def _oscillator(bench, ranges, total, step, hist, **kw):
    out = list(B.load_balance(bench, ranges, total, step, hist, **kw))
    _osc_flip[0] = not _osc_flip[0]
    if len(out) > 1:
        i, j = (0, 1) if _osc_flip[0] else (1, 0)
        if out[i] >= step:
            out[i] -= step
            out[j] += step
    return out


def _equal_seeder(total, step, priors, cid=None):
    """Prior seeding filed off: ignores the device-kind priors and
    hands back the equal split — from there, a 100x-skewed fleet's
    first damped rebalance lands far outside one step of the
    rate-implied split, which is exactly the churn the prior-seeded
    invariant exists to forbid."""
    return B.equal_split(int(total), len(priors), int(step))


def _balance_machine(alphabet=(1.0, 5.0), **kw):
    return M.BalanceMachine(rate_alphabet=alphabet, lane_counts=(2,),
                            horizon=24, **kw)


# -- resilience (serve/resilience.py) fixtures ------------------------------

def _double_probe_admit(state, now, open_s):
    """Half-open admits a SECOND probe while one is in flight."""
    out = R.breaker_admit(state, now, open_s)
    if state.get("state") == R.BREAKER_HALF_OPEN \
            and state.get("probe_inflight"):
        st = dict(out["state"])
        return dict(out, allow=True, probe=True, retry_after_s=None,
                    state=st)
    return out


def _eager_open(state, event, now, threshold, open_s):
    """Opens on the FIRST failure (threshold filed down to 1)."""
    out = R.breaker_transition(state, event, now, threshold, open_s)
    if state.get("state") == R.BREAKER_CLOSED and event == "failure" \
            and out["action"] is None:
        st = dict(out["state"], state=R.BREAKER_OPEN, opened_t=now)
        return {"state": st, "action": "opened"}
    return out


def _dishonest_hint(state, now, open_s):
    """Refusals carry a made-up hint instead of the remaining window."""
    out = R.breaker_admit(state, now, open_s)
    if not out["allow"]:
        return dict(out, retry_after_s=999.0)
    return out


def _never_half_open(state, now, open_s):
    """The open window never times out — admits are refused forever."""
    if state.get("state") == R.BREAKER_OPEN:
        return {"allow": False, "probe": False,
                "retry_after_s": float(open_s) / 2.0,
                "state": dict(state), "action": None}
    return R.breaker_admit(state, now, open_s)


def _probe_never_closes(state, event, now, threshold, open_s):
    """A successful probe re-opens instead of closing (permanent open
    under all-ok inputs)."""
    out = R.breaker_transition(state, event, now, threshold, open_s)
    if state.get("state") == R.BREAKER_HALF_OPEN and event == "success":
        st = dict(out["state"], state=R.BREAKER_OPEN, opened_t=now,
                  probe_inflight=False)
        return {"state": st, "action": "reopened"}
    return out


def _breaker_machine(**kw):
    return M.BreakerMachine(threshold=2, open_ticks=2, **kw)


def _hair_trigger_shed(state, qd, wm, cm, ob, dl, engage_streak=2):
    """Engages on the FIRST pressured evaluation — the hysteresis the
    pressure gate exists to enforce, filed off."""
    out = R.brownout_transition(state, qd, wm, cm, ob, dl,
                                engage_streak=engage_streak)
    if not state.get("active") and out["pressure"] and not out["active"]:
        return dict(out, active=True, streak=0, changed=True)
    return out


def _sticky_shed(state, qd, wm, cm, ob, dl, engage_streak=2):
    """Never releases: degraded mode is permanent."""
    out = R.brownout_transition(state, qd, wm, cm, ob, dl,
                                engage_streak=engage_streak)
    if state.get("active"):
        return dict(out, active=True, changed=False)
    return out


def _shed_everyone(**kw):
    """Sheds even a tenant with ZERO requests in flight."""
    dec = A.admit_decision(**kw)
    if kw.get("brownout") and dec["admit"]:
        return {"admit": False, "reason": A.REJECT_BROWNOUT,
                "retry_after_s": 0.1}
    return dec


def _anonymous_shed(**kw):
    """Brownout rejections renamed to the quota reason (and a
    busy-loop hint)."""
    dec = A.admit_decision(**kw)
    if dec.get("reason") == A.REJECT_BROWNOUT:
        return dict(dec, reason=A.REJECT_QUOTA, retry_after_s=0.0)
    return dec


def _shed_machine(**kw):
    return M.ShedMachine(engage_streak=2, **kw)


def _budgetless_retry(attempt, max_attempts, tokens, deadline_left_s,
                      base_s, cap_s, jitter_u):
    """Grants retries with an empty budget and past max_attempts —
    the retry storm the budget exists to prevent."""
    rd = R.retry_decision(attempt, max_attempts, tokens,
                          deadline_left_s, base_s, cap_s, jitter_u)
    if not rd["retry"] and rd["reason"] in ("budget-exhausted",
                                            "attempts-exhausted"):
        return {"retry": True, "delay_s": base_s, "reason": None}
    return rd


def _unbounded_backoff(attempt, max_attempts, tokens, deadline_left_s,
                       base_s, cap_s, jitter_u):
    """Backoff cap filed off: granted delays blow past 1.5×cap (and
    any deadline)."""
    rd = R.retry_decision(attempt, max_attempts, tokens,
                          deadline_left_s, base_s, cap_s, jitter_u)
    if rd["retry"]:
        return dict(rd, delay_s=10.0 * cap_s)
    return rd


def _retry_machine(**kw):
    return M.RetryMachine(max_attempts=2, budget_cap=2, **kw)


def _illegal_block_decide(current, walls, grid, hysteresis=0.08,
                          seed=None, fallback=None):
    """Engages a tile pair outside the legal grid — the unclamped
    store-inherited pair the clamp exists to snap."""
    choice, why = BT.block_transition(current, walls, grid,
                                      hysteresis=hysteresis, seed=seed,
                                      fallback=fallback)
    if choice is not None:
        return (64, 96), why
    return choice, why


def _flappy_block_decide(current, walls, grid, hysteresis=0.08,
                         seed=None, fallback=None):
    """Hysteresis filed off: always engages the instantaneous argmin,
    so a ±noise re-measure flaps the choice (and the executable cache
    behind it)."""
    gset = set(grid)
    known = sorted((tuple(p), float(w)) for p, w in walls
                   if tuple(p) in gset)
    if not known:
        return BT.block_transition(current, walls, grid,
                                   hysteresis=hysteresis, seed=seed,
                                   fallback=fallback)
    best = min(known, key=lambda kv: (kv[1], kv[0]))
    cur = None if current is None else tuple(current)
    return best[0], ("steady" if best[0] == cur else "model")


def _stale_block_emit(row):
    """Records the OUTGOING pair on a retune — the decision log
    misstates what actually engaged (a retune that is visible in name
    only; retune-visibility demands the row match the new choice)."""
    cur = row["inputs"].get("current") or [0, 0]
    return [dict(row, outputs=dict(row["outputs"],
                                   block_q=cur[0], block_k=cur[1]))]


def _block_machine(**kw):
    return M.BlockMachine(**kw)


class _FlipRoute:
    """Alternate calls bounce the same key between members — the
    drive/re-drive comparison (and any replay) diverges."""

    def __init__(self):
        self.calls = 0

    def __call__(self, tenant, key, members, unhealthy=(), epoch=0):
        out = F.route_decision(tenant, key, members, unhealthy, epoch)
        self.calls += 1
        roster = sorted(set(str(m) for m in members),
                        key=lambda m: (len(m), m))
        if out["shard"] is not None and len(roster) > 1 and \
                self.calls % 2:
            alt = roster[(roster.index(out["shard"]) + 1) % len(roster)]
            return dict(out, shard=alt, owner=alt)
        return out


def _modulo_route(tenant, key, members, unhealthy=(), epoch=0):
    """Placement by hash MOD roster size — the NON-consistent hash
    minimal-reshuffle exists to forbid: one departure reshuffles keys
    between the survivors."""
    import hashlib as _hl

    roster = sorted(set(str(m) for m in members),
                    key=lambda m: (len(m), m))
    if not roster:
        return F.route_decision(tenant, key, members, unhealthy, epoch)
    h = int(_hl.sha256(f"{tenant}|{key}".encode()).hexdigest()[:16], 16)
    owner = roster[h % len(roster)]
    bad = set(str(m) for m in unhealthy)
    shard, hops = None, 0
    for i in range(len(roster)):
        m = roster[(h + i) % len(roster)]
        if m not in bad:
            shard = m
            break
        hops += 1
    if shard is None:
        return {"shard": None, "owner": owner, "diverted": True,
                "hops": hops, "reason": F.REJECT_SHARD,
                "epoch": int(epoch)}
    return {"shard": shard, "owner": owner, "diverted": shard != owner,
            "hops": hops, "reason": None, "epoch": int(epoch)}


def _offroster_route(tenant, key, members, unhealthy=(), epoch=0):
    """Names a shard that is not in the roster."""
    out = F.route_decision(tenant, key, members, unhealthy, epoch)
    if out["shard"] is not None:
        return dict(out, shard="zz", owner="zz")
    return out


def _silent_divert_route(tenant, key, members, unhealthy=(), epoch=0):
    """Diverts off a sick owner WITHOUT the diverted flag / hop count
    — the silent diversion the named-decision rule forbids."""
    out = F.route_decision(tenant, key, members, unhealthy, epoch)
    if out["shard"] is not None and out["diverted"]:
        return dict(out, diverted=False, hops=0)
    return out


def _router_machine(**kw):
    return M.RouterMachine(member_ids=("p0", "p2"), **kw)


#: invariant id -> machine factory with the broken seam injected.
BROKEN_FIXTURES = {
    "breaker-half-open-one-probe":
        lambda: _breaker_machine(admit=_double_probe_admit),
    "breaker-opens-on-threshold":
        lambda: _breaker_machine(transition=_eager_open),
    "breaker-honest-hint":
        lambda: _breaker_machine(admit=_dishonest_hint),
    "breaker-open-times-out":
        lambda: _breaker_machine(admit=_never_half_open),
    "breaker-recovers-on-ok":
        lambda: _breaker_machine(transition=_probe_never_closes),
    "shed-pressure-gated":
        lambda: _shed_machine(transition=_hair_trigger_shed),
    "shed-quota-floor": lambda: _shed_machine(decide=_shed_everyone),
    "shed-named-hint": lambda: _shed_machine(decide=_anonymous_shed),
    "shed-releases": lambda: _shed_machine(transition=_sticky_shed),
    "retry-budget-bounded":
        lambda: _retry_machine(decide=_budgetless_retry),
    "retry-backoff-bounded":
        lambda: _retry_machine(decide=_unbounded_backoff),
    "availability-floor": lambda: _drain_machine(transition=_no_floor),
    "share-conservation": lambda: _drain_machine(masker=_leaky_masker),
    "quarantine-masked":
        lambda: _drain_machine(masker=_double_probe_masker),
    "action-visibility": lambda: _drain_machine(transition=_silent_drain),
    "eventual-readmission":
        lambda: _drain_machine(transition=_never_readmit),
    "no-silent-flap": lambda: _drain_machine(transition=_flappy),
    "epoch-monotone":
        lambda: _elastic_machine(membership_cls=_DoubleEpoch),
    "resplit-conservation": "monkeypatch",  # handled below
    "resplit-quantized": "monkeypatch",
    "sync-converges": lambda: _elastic_machine(membership_cls=_NoJoins),
    # needs >= 2 simultaneous departures for the within-phase order to
    # vary, so a 3-member alphabet
    "deterministic-order": lambda: M.ElasticMachine(
        member_ids=("p0", "p2", "p10"), steps=(2, 3),
        membership_cls=_FlakyOrder),
    "quota-exact": lambda: _admission_machine(decide=_quota_off_by_one),
    "queue-bounded": lambda: _admission_machine(decide=_no_queue_gate),
    "reject-order": lambda: _admission_machine(decide=_wrong_order),
    "retry-hint": lambda: _admission_machine(decide=_kernel_backoff),
    "admit-iff": lambda: _admission_machine(decide=_moody),
    "promoted-are-starved": lambda: _coalesce_machine(plan=_overpromote),
    "plan-complete": lambda: _coalesce_machine(plan=_order_dropper),
    "plan-deterministic": lambda: _coalesce_machine(plan=_jitter),
    "bounded-starvation": lambda: _coalesce_machine(plan=_no_fairness),
    "range-conservation":
        lambda: _balance_machine(balance=_lossy_balance),
    "range-quantized":
        lambda: _balance_machine(balance=_unquantized_balance),
    "jump-one-shot": lambda: _balance_machine(balance=_rejump_balance),
    "freeze-legal":
        lambda: _balance_machine(alphabet=(1.0,), balance=_freeze_mover),
    "converges": lambda: _balance_machine(balance=_oscillator),
    "prior-seeded-jump-within-one-step":
        lambda: _balance_machine(alphabet=(1.0, 100.0),
                                 seeder=_equal_seeder),
    "choice-legality":
        lambda: _block_machine(decide=_illegal_block_decide),
    "hysteresis-bound":
        lambda: _block_machine(decide=_flappy_block_decide),
    "retune-visibility":
        lambda: _block_machine(emit=_stale_block_emit),
    "placement-deterministic":
        lambda: _router_machine(route=_FlipRoute()),
    # mod-N reshuffling only shows between SURVIVORS, so a 3-member
    # alphabet (a 2-member roster's departure leaves nothing to
    # reshuffle between)
    "minimal-reshuffle": lambda: M.RouterMachine(
        member_ids=("p0", "p2", "p10"), route=_modulo_route),
    "routes-to-members":
        lambda: _router_machine(route=_offroster_route),
    "diversion-named":
        lambda: _router_machine(route=_silent_divert_route),
}


def test_fixture_table_covers_every_declared_invariant():
    declared = set()
    for mod in (D, E, A, C, B, R, BT, F):
        declared |= {row[0] for row in mod.MODEL_INVARIANTS}
    assert set(BROKEN_FIXTURES) == declared


@pytest.mark.parametrize("inv_id", sorted(BROKEN_FIXTURES))
def test_broken_fixture_produces_counterexample(inv_id, monkeypatch):
    factory = BROKEN_FIXTURES[inv_id]
    if factory == "monkeypatch":
        _real_resplit = E.member_resplit

        if inv_id == "resplit-conservation":
            def tampered(steps, total):
                out = _real_resplit(steps, total)
                if len(out["ranges"]) >= 2 and \
                        out["ranges"][0] >= out["lcm"]:
                    out = dict(out, ranges=[
                        out["ranges"][0] - out["lcm"],
                        *out["ranges"][1:]])
                return out
        else:
            def tampered(steps, total):
                out = _real_resplit(steps, total)
                if len(out["ranges"]) >= 2 and out["ranges"][0] >= 1:
                    rs = list(out["ranges"])
                    rs[0] -= 1
                    rs[-1] += 1
                    out = dict(out, ranges=rs)
                return out
        monkeypatch.setattr(E, "member_resplit", tampered)
        machine = _elastic_machine()
    else:
        machine = factory()
    report = machine.explore()
    hit = [v for v in report["violations"] if v.invariant == inv_id]
    assert hit, (
        f"broken fixture for {inv_id} produced no violation; got "
        f"{[v.invariant for v in report['violations']]}")
    v = hit[0]
    assert v.fingerprint and v.machine and v.kind in ("safety",
                                                      "liveness")
    assert v.trace, f"{inv_id}: counterexample trace is empty"
    assert all({"seq", "kind", "inputs", "outputs"} <= set(r)
               for r in v.trace)


# ---------------------------------------------------------------------------
# 3. the counterexample -> replay bridge
# ---------------------------------------------------------------------------

def test_broken_drain_trace_diverges_under_replay():
    """A counterexample from a broken fixture machine carries the
    BROKEN outputs; replaying it through the real drain_transition
    names the first divergent seq — the ckreplay tamper drill, fed by
    the model checker."""
    report = _drain_machine(transition=_no_floor).explore()
    v = next(x for x in report["violations"]
             if x.invariant == "availability-floor")
    verdict = verify_counterexample(v)
    assert verdict["ok"] is False
    assert verdict["first_divergence"] is not None
    assert verdict["first_divergence"]["seq"] >= 1
    assert verdict["first_divergence"]["kind"] in ("drain-apply",
                                                   "readmit")


def test_broken_block_trace_diverges_under_replay():
    """The block tamper drill: a hysteresis-free chooser's
    counterexample carries flapped outputs; replaying through the real
    block_transition names the first divergent seq."""
    report = _block_machine(decide=_flappy_block_decide).explore()
    v = next(x for x in report["violations"]
             if x.invariant == "hysteresis-bound")
    verdict = verify_counterexample(v)
    assert verdict["ok"] is False
    assert verdict["first_divergence"] is not None
    assert verdict["first_divergence"]["seq"] >= 1
    assert verdict["first_divergence"]["kind"] == "block-retune"


def test_real_machine_trace_replays_green():
    """A trace assembled from the REAL controller functions replays
    bit-identically — committing one as a fixture pins fixed behavior."""
    report = _balance_machine(balance=_oscillator).explore()
    v = next(x for x in report["violations"]
             if x.invariant == "converges")
    # the records are the real load_balance emissions (the oscillator
    # tampers only the fed-back ranges, which become the next record's
    # INPUTS) — so the trace itself must verify clean
    verdict = verify_counterexample(v)
    assert verdict["ok"] is True
    assert verdict["replayed"] == len(v.trace)


def test_counterexample_spills_and_rides_ckreplay(tmp_path, capsys):
    """End-to-end acceptance pin: a counterexample trace saved by the
    bridge is a ck-decision-log-v1 jsonl that `ckreplay verify` exits 0
    on and `ckreplay explain` renders a causality table from."""
    import tools.ckreplay as ckreplay

    report = _balance_machine(balance=_oscillator).explore()
    v = next(x for x in report["violations"]
             if x.invariant == "converges")
    path = str(tmp_path / "counterexample.jsonl")
    assert save_counterexample(path, v) == path
    # the decision-log loader reads it (schema header + rows)
    records = load_decision_log(path)
    assert len(records) == len(v.trace)
    assert ckreplay.main(["verify", path]) == 0
    out = capsys.readouterr().out
    assert "replay-verify" in out or "OK" in out or "ok" in out.lower()
    assert ckreplay.main(["explain", path]) == 0
    out = capsys.readouterr().out
    assert "lane" in out  # the per-lane causality table rendered


def test_save_counterexample_normalizes_partial_rows(tmp_path):
    """The ONE trace normalizer (obs/replay + DecisionRecord.from_row):
    partial rows — no clocks, no inputs — spill and load cleanly."""
    p = str(tmp_path / "t.jsonl")
    save_counterexample(p, {"trace": [
        {"kind": "coalesce", "seq": 1, "inputs": {"a": 1},
         "outputs": {"b": 2}},
        {"kind": "coalesce", "seq": 2},
    ]})
    records = load_decision_log(p)
    assert [r.seq for r in records] == [1, 2]
    assert records[1].inputs == {} and records[1].outputs == {}


# ---------------------------------------------------------------------------
# 4. real violations found by the checker, fixed in this PR, pinned
# ---------------------------------------------------------------------------

def test_swap_cycle_fixture_replays_bit_identically():
    """The balancer ±1-step swap limit cycle (found by ckmodel, fixed
    by REPAIR_TIE_BAND's incumbent tie-break): the committed trace was
    recorded from the FIXED code, so replaying it fails if anyone
    reverts the repair-loop semantics."""
    records = load_decision_log(SWAP_CYCLE_FIXTURE)
    assert len(records) >= 10
    verdict = verify_records(records)
    assert verdict["ok"] is True, verdict["first_divergence"]
    assert verdict["replayed"] == len(records)


def test_swap_cycle_scenario_converges_live():
    """Live re-drive of the counterexample scenario: two equal-rate
    lanes + one 8x-slower lane, jump on.  Pre-fix, the repair step
    flipped between the equal pair forever ([1536,1408,128] <->
    [1408,1536,128]); the split must now settle and stay."""
    total, step, rates = 3072, 128, (1.0, 1.0, 8.0)
    state = B.BalanceState()
    ranges = B.equal_split(total, 3, step)
    state.reset(ranges, B.DAMPING)
    tail = []
    for _ in range(40):
        bench = [rates[i] * max(ranges[i], step) for i in range(3)]
        ranges = B.load_balance(bench, list(ranges), total, step, None,
                                state=state, jump_start=True, cid=0)
        tail.append(tuple(ranges))
    assert len(set(tail[-10:])) == 1, tail[-10:]
    assert sum(tail[-1]) == total


#: The concrete G=4 schedule the checker's probe found: all four
#: groups pending for six rounds starved g1 SIX consecutive cycles
#: under the old whole-list rotation (anchor re-aimed as the streak
#: resized).  The fixed longest-starved-first promotion bounds it.
OLD_ROTATION_SCHEDULE = [
    ("g0", "g1", "g2", "g3")] * 6 + [
    ("g0",), ("g0", "g2", "g3"), ("g0", "g2"), ("g0", "g2")]


def _drive_coalesce(schedule, mp, G=4):
    keys = [f"g{i}" for i in range(G)]
    ages = {k: float(G - i) for i, k in enumerate(keys)}
    starved = {k: 0 for k in keys}
    worst = 0
    for rnd, present in enumerate(schedule):
        rows = sorted(
            ({"key": k, "pending": 1, "deadline_in_s": None,
              "oldest_age_s": ages[k], "starved_rounds": starved[k]}
             for k in present), key=lambda r: r["key"])
        picked = set(C.plan_coalesce(rows, rnd, mp)["picked"])
        for k in keys:
            if k not in present or k in picked:
                starved[k] = 0
            else:
                starved[k] += 1
            worst = max(worst, starved[k])
    return worst


def test_rotation_starvation_counterexample_now_bounded():
    worst = _drive_coalesce(OLD_ROTATION_SCHEDULE, mp=1)
    bound = C.STARVE_ROUNDS + (4 - 1)
    assert worst <= bound, (
        f"the pinned G=4 schedule starved a group {worst} consecutive "
        f"cycles (bound {bound}) — the longest-starved-first promotion "
        "regressed")


def test_plan_coalesce_fairness_property():
    """Satellite: randomized arrival/desertion/deadline histories must
    respect the capacity-aware starvation bound — STARVE_ROUNDS when
    max_picks covers the streak, STARVE_ROUNDS + (G-1) at max_picks=1
    (the exact guarantee the r10-era k-member rotation violated)."""
    for G, mp, seeds in ((3, 1, 6), (4, 1, 6), (5, 2, 4), (4, 3, 4)):
        bound = C.STARVE_ROUNDS + (G - 1 if mp < G - 1 else 0)
        keys = [f"g{i}" for i in range(G)]
        for seed in range(seeds):
            rng = random.Random(seed * 37 + G * 5 + mp)
            present = set(keys)
            schedule = []
            for _ in range(400):
                for k in keys[1:]:
                    if rng.random() < 0.3:
                        present.symmetric_difference_update({k})
                present.add(keys[0])
                schedule.append(tuple(sorted(present)))
            worst = _drive_coalesce(schedule, mp=mp, G=G)
            assert worst <= bound, (G, mp, seed, worst, bound)


# ---------------------------------------------------------------------------
# 5. CLI lifecycle (ratchet, provenance, --json, --save-trace)
# ---------------------------------------------------------------------------

def _fake_violation():
    return M.ModelViolation(
        "drain", "availability-floor", "safety",
        "fixture: no active lane left", {"lanes": {"0": "quarantined"}},
        [{"kind": "drain-apply", "inputs": {"verdicts": {}},
          "outputs": {"drained": ["0"]}}])


def _patch_analyze(monkeypatch, findings):
    def fake(machine=None, scale=None):
        report = {
            "ok": not findings,
            "states_explored": 123, "transitions": 45,
            "machines": {"drain": {
                "states_explored": 123, "transitions": 45,
                "truncated": False, "violations": list(findings),
                "sub_machines": {}}},
            "violations": list(findings),
        }
        return list(findings), report
    monkeypatch.setattr(ckmodel_cli, "analyze", fake)


def test_cli_ratchet_lifecycle(tmp_path, monkeypatch, capsys):
    baseline = str(tmp_path / "b.json")
    v = _fake_violation()
    _patch_analyze(monkeypatch, [v])
    args = ["--baseline", baseline]

    # (1) a new finding fails, naming machine + invariant
    assert ckmodel_cli.main(args) == 1
    out = capsys.readouterr().out
    assert "availability-floor" in out and "NEW" in out

    # (2) --update-baseline refuses growth without --allow-grow
    assert ckmodel_cli.main(args + ["--update-baseline"]) == 1
    assert "REFUSING" in capsys.readouterr().out
    assert ckmodel_cli.main(
        args + ["--update-baseline", "--allow-grow"]) == 0
    capsys.readouterr()
    assert ckmodel_cli.main(args) == 0  # grandfathered
    capsys.readouterr()

    # (3) --explain renders the counterexample + rule doc
    assert ckmodel_cli.main(args + ["--explain", v.fingerprint]) == 0
    out = capsys.readouterr().out
    assert "counterexample" in out and "drain-apply" in out
    assert "grandfathered" in out

    # (4) fixing without shrinking -> stale, naming the burn commit
    _patch_analyze(monkeypatch, [])
    assert ckmodel_cli.main(args) == 1
    out = capsys.readouterr().out
    assert "STALE" in out and "baseline burned by ckmodel" in out

    # (5) the shrink: clean again
    assert ckmodel_cli.main(args + ["--update-baseline"]) == 0
    capsys.readouterr()
    assert ckmodel_cli.main(args) == 0
    assert "clean" in capsys.readouterr().out


def test_cli_json_schema_and_save_trace(tmp_path, monkeypatch, capsys):
    baseline = str(tmp_path / "b.json")
    v = _fake_violation()
    _patch_analyze(monkeypatch, [v])
    tr = str(tmp_path / "traces")
    rc = ckmodel_cli.main(["--baseline", baseline, "--json",
                           "--save-trace", tr])
    out = capsys.readouterr().out
    assert rc == 1
    doc = json.loads(out[out.index("{"):])
    assert {"new", "grandfathered", "stale_baseline",
            "states_explored", "transitions", "machines"} <= set(doc)
    row = doc["new"][0]
    assert {"fingerprint", "machine", "invariant", "kind", "message",
            "state", "trace_len"} <= set(row)
    # the spilled trace is a loadable decision log
    spilled = os.path.join(tr, f"{v.fingerprint}.jsonl")
    assert os.path.exists(spilled)
    assert len(load_decision_log(spilled)) == 1


def test_cli_explain_provenance(capsys):
    assert ckmodel_cli.main(["--explain", "provenance"]) == 0
    out = capsys.readouterr().out
    assert "baseline burned by ckmodel" in out


def test_checked_in_baselines_carry_provenance():
    """Satellite: all three ratchet baselines (ckcheck, ckprove,
    ckmodel) share the provenance header naming tool + burn commit."""
    for rel, tool in (("tools/ckcheck/baseline.json", "ckcheck"),
                      ("tools/ckprove_baseline.json", "ckprove"),
                      ("tools/ckmodel/baseline.json", "ckmodel")):
        with open(os.path.join(ROOT, rel)) as f:
            doc = json.load(f)
        prov = doc.get("provenance")
        assert prov, f"{rel} has no provenance header"
        assert prov["tool"] == tool
        assert prov["head"] and prov["head"] != "unknown"
        assert prov["updated_at"]
        assert doc["findings"] == []  # all three expected-empty


def test_stale_baseline_names_burn_commit(tmp_path, monkeypatch, capsys):
    """The satellite's motivating failure: a stale ratchet entry now
    names the commit the baseline was burned at."""
    from tools.ckcheck.baseline import provenance_note, save_baseline

    b = str(tmp_path / "b.json")
    save_baseline(b, [_fake_violation()], tool="ckmodel")
    note = provenance_note(json.load(open(b)))
    assert "baseline burned by ckmodel" in note
    assert "at commit" in note
    # a pre-provenance baseline degrades with a named reason
    legacy = str(tmp_path / "old.json")
    json.dump({"schema": "ckcheck-baseline-v1", "findings": []},
              open(legacy, "w"))
    assert "no provenance header" in provenance_note(
        json.load(open(legacy)))


# ---------------------------------------------------------------------------
# 6. purity lint
# ---------------------------------------------------------------------------

def test_purity_clean_on_head():
    findings = purity.run(ROOT)
    assert findings == [], [f.render() for f in findings]


def test_purity_flags_clock_and_global_reads():
    src = (
        "import time\n"
        "from x import DECISIONS\n"
        "_cache = {}\n"
        "def trans(a):\n"
        "    _cache[a] = time.time()\n"
        "    DECISIONS.record('x')\n"
        "    return helper(a)\n"
        "def helper(a):\n"
        "    return a + perf_counter()\n"
    )
    findings = purity.scan_module(src, "mod.py", ("trans",), ())
    rules = {(f.func, f.rule) for f in findings}
    assert ("trans", "impure-call") in rules
    assert ("trans", "impure-global") in rules
    assert ("helper", "impure-call") in rules  # transitive closure
    msgs = " ".join(f.message for f in findings)
    assert "_cache" in msgs and "DECISIONS" in msgs


def test_purity_seam_allows_declared_dependency():
    src = (
        "from other import Helper\n"
        "def trans(a):\n"
        "    return Helper(a).go()\n"
    )
    assert purity.scan_module(src, "m.py", ("trans",), ("Helper",)) == []
    flagged = purity.scan_module(src, "m.py", ("trans",), ())
    assert flagged and flagged[0].rule == "impure-global"


def test_purity_missing_declared_function_is_a_finding(tmp_path):
    mod = tmp_path / "pkg.py"
    mod.write_text("def exists(a):\n    return a\n")
    findings = purity.run(str(tmp_path), table=(
        ("pkg.py", ("exists", "vanished"), ()),))
    assert any(f.rule == "missing" and f.func == "vanished"
               for f in findings)


def test_purity_constants_and_helpers_allowed():
    src = (
        "LIMIT = 3\n"
        "_FLOOR_S = 0.5\n"
        "def trans(a):\n"
        "    return [clip(v) for v in a][:LIMIT]\n"
        "def clip(v):\n"
        "    return max(v, _FLOOR_S)\n"
    )
    assert purity.scan_module(src, "m.py", ("trans",), ()) == []


# ---------------------------------------------------------------------------
# 8. decisions capture seam (the checker's isolation contract)
# ---------------------------------------------------------------------------

def test_capture_isolates_the_live_ring():
    from cekirdekler_tpu.obs.decisions import DECISIONS

    before = DECISIONS.snapshot()
    total_before = DECISIONS.total_recorded
    with DECISIONS.capture() as ring:
        DECISIONS.record("coalesce", {"groups": []}, {"order": []})
        assert len(ring) == 1
        assert DECISIONS.snapshot()[-1].kind == "coalesce"
    after = DECISIONS.snapshot()
    assert [r.seq for r in after] == [r.seq for r in before]
    assert DECISIONS.total_recorded == total_before
