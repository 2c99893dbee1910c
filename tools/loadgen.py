#!/usr/bin/env python
"""Serving-tier load generator: N concurrent clients against one
``ServeFrontend`` (docs/SERVING.md), open- or closed-loop.

Run from the repo root:

    python tools/loadgen.py [--clients 32] [--tenants 4] [--signatures 4]
                            [--requests 8] [--mode closed|open|both|chaos]
                            [--rate 200] [--n 16384] [--fabric N] [--json]

- **closed loop**: every client submits its next request only after the
  previous one resolved — the latency-under-concurrency measurement
  (``p50_ms`` / ``p99_ms`` headline keys).
- **open loop**: clients submit at a fixed per-client rate without
  waiting (rejections count, retries honor ``retry_after_s``) — the
  goodput measurement (``goodput_rps``: completed requests per second
  of wall).

Either way the run reports the **coalescing evidence**: requests vs
actual ladder dispatches (fused windows + per-call iterations, read as
``ck_fused_*`` counter deltas) as ``coalesce_ratio`` — the "N requests
collapse into measurably fewer ladder launches" number the ROADMAP
acceptance names — and verifies the workload bit-exactly (every
signature's array must equal its completed-request count; the inc
kernel makes lost/duplicated requests integer-visible).

:func:`loadgen_section` (``--mode both``) is one closed run, one open run
and the chaos sub-run with the headline floats hoisted; no ledger cell
drives it yet.

``--fabric N`` shards the front-end: the same closed-loop workload runs
against a :class:`~cekirdekler_tpu.serve.ServeFabric` of N member
shards (docs/SERVING.md, "Cluster fabric") and, for ``--mode chaos``,
a seeded mid-run member kill whose in-flight requests must re-route
onto the survivors bit-exactly (:func:`run_fabric_chaos`);
:func:`fabric_section` is both beside the single-frontend baseline.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

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


#: The workload kernel: +1.0f per request — small-integer f32 math is
#: exact, so the post-run check can demand bit equality between each
#: array and its signature's completed-request count.
LOADGEN_SRC = """
__kernel void lg_inc(__global float* a) {
    int i = get_global_id(0);
    a[i] = a[i] + 1.0f;
}
"""


def _percentile(sorted_vals: list, q: float) -> float:
    """Nearest-rank percentile of an ASCENDING list (no numpy — the
    tool must import light)."""
    if not sorted_vals:
        return 0.0
    k = max(0, min(len(sorted_vals) - 1,
                   int(round(q * (len(sorted_vals) - 1)))))
    return float(sorted_vals[k])


def _run_anatomy(t_wall0: float) -> dict:
    """Fold this run's request-lifecycle events (obs/reqtrace.py) into
    the tail-anatomy block every run result carries: the p50/p95/p99
    per-phase decomposition plus the p99 queue/device fractions.
    The recorder ring is process-global, so
    the fold is WALL-clock-bounded to ``t_wall0`` — earlier runs'
    events (warmups, the chaos control) must not blend in."""
    from cekirdekler_tpu.obs.reqtrace import (
        REQTRACE, fold_phases, phase_fracs, tail_anatomy)

    events = [e for e in REQTRACE.snapshot() if e.t >= t_wall0]
    records = [r for r in fold_phases(events) if r["outcome"] == "resolved"]
    anatomy = tail_anatomy(records)
    fr: dict = {}
    p99 = anatomy["pcts"].get("p99")
    if p99 is not None:
        by_rid = {r["rid"]: r for r in records}
        fr = phase_fracs(by_rid[p99["rid"]])
    return {
        "anatomy": anatomy,
        "p99_queue_frac": fr.get("queue_frac"),
        "p99_device_frac": fr.get("device_frac"),
    }


def _print_anatomy(out: dict, label: str = "") -> None:
    """Render a run result's tail-anatomy table (printed after EVERY
    human-readable run — the per-phase answer to "where did the p99
    millisecond budget go")."""
    anatomy = out.get("anatomy")
    if not isinstance(anatomy, dict) or not anatomy.get("count"):
        return
    from cekirdekler_tpu.obs.reqtrace import anatomy_table

    suffix = f" ({label})" if label else ""
    print(f"  -- tail anatomy{suffix} --")
    for line in anatomy_table(anatomy).splitlines():
        print(f"  {line}")


#: The default seeded chaos plan (``--mode chaos``; docs/RESILIENCE.md
#: "Serving resilience"): bounded driver-submit failures (exercises
#: blast-radius containment + retry budgets), one lane stalling at
#: barriers, and one slow link — the three failure shapes the serving
#: tier must survive with goodput intact.
CHAOS_PLAN = ("seed=42;driver-submit:after=2,times=3;"
              "lane-stall@lane1:delay_ms=25,times=3;"
              "slow-link@lane1:factor=3,times=10")


def run_loadgen(
    devices=None,
    clients: int = 32,
    tenants: int = 4,
    signatures: int = 4,
    requests_per_client: int = 8,
    mode: str = "closed",
    rate_rps: float = 200.0,
    n: int = 1 << 14,
    local_range: int = 64,
    gather_window_s: float = 0.004,
    max_batch: int = 512,
    quota: int = 0,
    max_queue_depth: int = 0,
    max_retries: int = 50,
    resilience=None,
    pin_sig: bool = False,
) -> dict:
    """One load-generator run (see module docstring).  Returns the
    result dict with p50/p99 latency, goodput, the coalescing evidence,
    and the exactness check.  Under an armed fault plan the result also
    carries the chaos evidence: ``hangs`` (futures that never resolved
    — must be 0), ``unnamed_failures`` (failures without a framework-
    named cause — must be 0), and ``failure_causes``."""
    import numpy as np

    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.errors import CekirdeklerError
    from cekirdekler_tpu.hardware import all_devices
    from cekirdekler_tpu.metrics.registry import REGISTRY
    from cekirdekler_tpu.serve import (
        AdmissionController,
        ServeFrontend,
        ServeJob,
        ServeRejected,
    )

    if mode not in ("closed", "open"):
        raise ValueError(f"mode must be closed|open, got {mode!r}")
    devs = devices if devices is not None else all_devices().cpus()
    devs = devs.subset(min(2, len(devs)) or 1)
    clients = max(1, int(clients))
    tenants = max(1, int(tenants))
    signatures = max(1, int(signatures))
    total_target = clients * max(1, int(requests_per_client))

    cr = NumberCruncher(devs, LOADGEN_SRC)
    arrays = []
    jobs = []
    for s in range(signatures):
        a = ClArray(np.zeros(n, np.float32), name=f"lg{s}")
        a.partial_read = True
        arrays.append(a)
        jobs.append(ServeJob(
            params=[a], kernels=["lg_inc"], compute_id=9100 + s,
            global_range=n, local_range=local_range,
        ))
    admission = AdmissionController(
        max_queue_depth=(int(max_queue_depth) if max_queue_depth
                         else max(64, 4 * total_target)),
        default_quota=(int(quota) if quota else max(8, total_target)),
        health=cr.cores.health.healthy,
    )
    fe = ServeFrontend(
        cr, admission=admission, max_batch=max_batch,
        gather_window_s=gather_window_s, name=f"loadgen-{mode}",
        resilience=resilience,
    )

    m_windows = REGISTRY.counter(
        "ck_fused_windows_total", "fused ladder dispatch batches")
    m_iters = REGISTRY.counter(
        "ck_fused_iters_total", "iterations dispatched via fused ladders")
    w0, i0 = m_windows.value, m_iters.value

    latencies: list[float] = []
    completed_per_sig = [0] * signatures
    rejected = [0]
    retries_exhausted = [0]
    failed = [0]
    hangs = [0]
    unnamed = [0]
    failure_causes: dict = {}
    mu = threading.Lock()

    def submit_with_retry(tenant: str, job: ServeJob):
        """Submit honoring retry-after (the admission contract's client
        half); returns the future or None when retries ran out."""
        for _ in range(max(1, int(max_retries))):
            try:
                return fe.submit(tenant, job)
            except ServeRejected as e:
                with mu:
                    rejected[0] += 1
                time.sleep(min(e.retry_after_s, 0.25))
        with mu:
            retries_exhausted[0] += 1
        return None

    from concurrent.futures import TimeoutError as _FutTimeout

    def note_done(fut, sig_idx: int):
        try:
            r = fut.result(timeout=60.0)
        except (TimeoutError, _FutTimeout):
            # the one outcome chaos must NEVER produce: a future that
            # does not resolve (counted separately from failures)
            with mu:
                hangs[0] += 1
            return
        except Exception as e:  # noqa: BLE001 - counted, checked below
            with mu:
                failed[0] += 1
                cause = type(e).__name__
                failure_causes[cause] = failure_causes.get(cause, 0) + 1
                if not isinstance(e, CekirdeklerError):
                    unnamed[0] += 1
            return
        with mu:
            latencies.append(r["latency_s"])
            completed_per_sig[sig_idx] += 1

    def client_closed(ci: int):
        tenant = f"t{ci % tenants}"
        for k in range(int(requests_per_client)):
            # pin_sig: one (tenant, signature) pair per client — the
            # fabric comparison's workload shape (placement is per
            # (tenant, signature), so a pinned client maps to one shard)
            sig_idx = ((ci // tenants) % signatures if pin_sig
                       else (ci + k) % signatures)
            fut = submit_with_retry(tenant, jobs[sig_idx])
            if fut is not None:
                note_done(fut, sig_idx)

    def client_open(ci: int):
        tenant = f"t{ci % tenants}"
        period = 1.0 / max(rate_rps / clients, 1e-3)
        pending = []
        for k in range(int(requests_per_client)):
            sig_idx = (ci + k) % signatures
            fut = submit_with_retry(tenant, jobs[sig_idx])
            if fut is not None:
                pending.append((fut, sig_idx))
            time.sleep(period)
        for fut, sig_idx in pending:
            note_done(fut, sig_idx)

    body = client_closed if mode == "closed" else client_open
    threads = [
        threading.Thread(target=body, args=(ci,), daemon=True,
                         name=f"lg-client-{ci}")
        for ci in range(clients)
    ]
    t_wall0 = time.time()  # reqtrace fold bound (see _run_anatomy)
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300.0)
    wall_s = time.perf_counter() - t0

    try:
        fe.close()
        # exactness: every signature's array must equal its completed
        # count exactly (each completed request applied +1 once)
        checked = all(
            bool(np.all(np.asarray(arrays[s]) == float(completed_per_sig[s])))
            for s in range(signatures)
        )
    finally:
        cr.dispose()

    completed = sum(completed_per_sig)
    windows = int(m_windows.value - w0)
    fused_iters = int(m_iters.value - i0)
    per_call = max(0, completed - fused_iters)
    launches = windows + per_call
    lat_ms = sorted(v * 1000.0 for v in latencies)
    return {
        "mode": mode,
        "platform": devs[0].platform,  # where the lanes ran (cpu default)
        "clients": clients,
        "tenants": tenants,
        "signatures": signatures,
        "requests_target": total_target,
        "completed": completed,
        "failed": failed[0],
        "hangs": hangs[0],
        "unnamed_failures": unnamed[0],
        "failure_causes": dict(sorted(failure_causes.items())),
        "rejected": rejected[0],
        "retries_exhausted": retries_exhausted[0],
        "wall_s": round(wall_s, 4),
        "p50_ms": round(_percentile(lat_ms, 0.50), 3),
        "p99_ms": round(_percentile(lat_ms, 0.99), 3),
        "goodput_rps": round(completed / wall_s, 2) if wall_s > 0 else None,
        # the coalescing evidence: ladder dispatches actually paid vs
        # requests served (windows = fused ladder batches, per_call =
        # iterations that rode the per-call path)
        "fused_windows": windows,
        "fused_iters": fused_iters,
        "per_call_iters": per_call,
        "ladder_launches": launches,
        "coalesce_ratio": (round(completed / launches, 3)
                           if launches > 0 else None),
        "coalesced": launches < completed,
        "checked": checked,
        **_run_anatomy(t_wall0),
    }


def run_chaos(devices=None, clients: int = 32, tenants: int = 4,
              signatures: int = 4, requests_per_client: int = 4,
              plan: str = CHAOS_PLAN, n: int = 1 << 13,
              goodput_floor: float = 0.5) -> dict:
    """The chaos acceptance drill (docs/RESILIENCE.md, "Serving
    resilience"): run the closed-loop workload FAULT-FREE (the control),
    then again under the seeded ``plan`` (driver-submit failures + lane
    stall + slow link), and check the four chaos contracts:

    - **no hangs** — every submitted future resolves;
    - **bit-exact** — every signature's array equals its successful
      count exactly (containment: a faulted request's iterations never
      half-apply);
    - **named failures** — every failure carries a framework-named
      cause (never a bare exception from the middle of a batch);
    - **goodput retained** — chaos goodput / control goodput clears
      ``goodput_floor``.

    ``checked`` is the conjunction; :func:`loadgen_section` reports
    ``chaos_goodput_frac`` / ``chaos_p99_ms`` only while it holds."""
    from cekirdekler_tpu.utils.faultinject import FAULTS

    # untimed warmup: the ladder compiles are process-global, so
    # without this the control run pays them and the chaos run does
    # not — goodput_frac would measure compile warmth, not resilience
    run_loadgen(devices, clients=4, tenants=tenants,
                signatures=signatures, requests_per_client=1,
                mode="closed", n=n)
    control = run_loadgen(
        devices, clients=clients, tenants=tenants,
        signatures=signatures, requests_per_client=requests_per_client,
        mode="closed", n=n)
    FAULTS.arm(plan)
    try:
        chaos = run_loadgen(
            devices, clients=clients, tenants=tenants,
            signatures=signatures,
            requests_per_client=requests_per_client, mode="closed", n=n)
    finally:
        FAULTS.disarm()
    frac = None
    if control.get("goodput_rps") and chaos.get("goodput_rps"):
        frac = round(chaos["goodput_rps"] / control["goodput_rps"], 4)
    checked = bool(
        control["checked"] and chaos["checked"]
        and chaos["hangs"] == 0 and chaos["unnamed_failures"] == 0
        and frac is not None and frac >= float(goodput_floor))
    return {
        "plan": plan,
        "goodput_frac": frac,
        "goodput_floor": goodput_floor,
        "chaos_p99_ms": chaos["p99_ms"],
        "hangs": chaos["hangs"],
        "failed": chaos["failed"],
        "unnamed_failures": chaos["unnamed_failures"],
        "failure_causes": chaos["failure_causes"],
        "checked": checked,
        "control": control,
        "chaos": chaos,
    }


def run_fabric(
    devices=None,
    fabric: int = 3,
    clients: int = 128,
    tenants: int = 8,
    signatures: int = 4,
    requests_per_client: int = 4,
    n: int = 1 << 13,
    local_range: int = 64,
    gather_window_s: float = 0.004,
    max_batch: int = 512,
    max_retries: int = 50,
    kill: bool = False,
    kill_after_frac: float = 0.25,
    seed: int = 2017,
) -> dict:
    """One closed-loop run against a ``ServeFabric`` of ``fabric``
    member shards.  Every (tenant, signature) pair owns its OWN array
    — the router places each such job on exactly one shard (placement
    hashes tenant + job signature), so no array is ever written by two
    dispatchers and the exactness check stays bit-level.

    With ``kill``, a seeded victim member is removed (no drain) once
    ``kill_after_frac`` of the target requests completed — the
    mid-run preemption drill.  Its queued requests fail with named
    clean-shutdown errors and the fabric re-routes them onto ring
    survivors; the contracts (zero hangs, named failures only,
    bit-exact arrays) are reported alongside the latency/goodput
    numbers."""
    import random as _random

    import numpy as np

    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.errors import CekirdeklerError
    from cekirdekler_tpu.hardware import all_devices
    from cekirdekler_tpu.metrics.registry import REGISTRY
    from cekirdekler_tpu.serve import ServeFabric, ServeJob, ServeRejected

    devs = devices if devices is not None else all_devices().cpus()
    devs = devs.subset(min(2, len(devs)) or 1)
    fabric = max(1, int(fabric))
    clients = max(1, int(clients))
    tenants = max(1, int(tenants))
    signatures = max(1, int(signatures))
    total_target = clients * max(1, int(requests_per_client))
    members = [f"m{i}" for i in range(fabric)]

    crunchers = {m: NumberCruncher(devs, LOADGEN_SRC) for m in members}
    fab = ServeFabric(
        crunchers, max_batch=max_batch, gather_window_s=gather_window_s,
        name="lg-fabric")
    arrays: dict = {}
    jobs: dict = {}
    for ti in range(tenants):
        for si in range(signatures):
            a = ClArray(np.zeros(n, np.float32), name=f"lgf{ti}_{si}")
            a.partial_read = True
            arrays[(ti, si)] = a
            jobs[(ti, si)] = ServeJob(
                params=[a], kernels=["lg_inc"],
                compute_id=9200 + ti * signatures + si,
                global_range=n, local_range=local_range,
            )

    m_windows = REGISTRY.counter(
        "ck_fused_windows_total", "fused ladder dispatch batches")
    m_reroutes = REGISTRY.counter(
        "ck_serve_fabric_reroutes_total",
        "in-flight requests re-routed onto ring survivors after a "
        "member preemption (budget-gated, clean failures only)")
    m_diverted = REGISTRY.counter(
        "ck_serve_fabric_diversions_total",
        "requests routed past an unhealthy owner to a ring successor")
    w0 = m_windows.value
    r0, d0 = m_reroutes.value, m_diverted.value

    latencies: list[float] = []
    completed: dict = {k: 0 for k in jobs}
    rejected = [0]
    retries_exhausted = [0]
    failed = [0]
    hangs = [0]
    unnamed = [0]
    failure_causes: dict = {}
    mu = threading.Lock()
    kill_trigger = threading.Event()
    kill_threshold = max(1, int(total_target * float(kill_after_frac)))
    victim = _random.Random(seed).choice(members) if kill else None
    killed_at = [None]

    def submit_with_retry(tenant: str, job):
        for _ in range(max(1, int(max_retries))):
            try:
                return fab.submit(tenant, job)
            except ServeRejected as e:
                with mu:
                    rejected[0] += 1
                time.sleep(min(e.retry_after_s, 0.25))
            except CekirdeklerError as e:
                # a shard dying between route and submit surfaces here
                # when re-route budgets are spent — named, retried
                with mu:
                    rejected[0] += 1
                    cause = type(e).__name__
                    failure_causes[cause] = failure_causes.get(cause, 0) + 1
                time.sleep(0.01)
        with mu:
            retries_exhausted[0] += 1
        return None

    from concurrent.futures import TimeoutError as _FutTimeout

    def note_done(fut, key):
        try:
            r = fut.result(timeout=60.0)
        except (TimeoutError, _FutTimeout):
            with mu:
                hangs[0] += 1
            return
        except Exception as e:  # noqa: BLE001 - counted, checked below
            with mu:
                failed[0] += 1
                cause = type(e).__name__
                failure_causes[cause] = failure_causes.get(cause, 0) + 1
                if not isinstance(e, CekirdeklerError):
                    unnamed[0] += 1
            return
        with mu:
            latencies.append(r["latency_s"])
            completed[key] += 1
            if sum(completed.values()) >= kill_threshold:
                kill_trigger.set()

    def client_closed(ci: int):
        ti = ci % tenants
        tenant = f"t{ti}"
        for k in range(int(requests_per_client)):
            key = (ti, (ci + k) % signatures)
            fut = submit_with_retry(tenant, jobs[key])
            if fut is not None:
                note_done(fut, key)

    def killer():
        kill_trigger.wait(timeout=120.0)
        killed_at[0] = sum(completed.values())
        fab.remove_member(victim, drain=False)

    threads = [
        threading.Thread(target=client_closed, args=(ci,), daemon=True,
                         name=f"lgf-client-{ci}")
        for ci in range(clients)
    ]
    if victim is not None:
        threads.append(threading.Thread(
            target=killer, daemon=True, name="lgf-killer"))
    t_wall0 = time.time()  # reqtrace fold bound (see _run_anatomy)
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300.0)
    wall_s = time.perf_counter() - t0

    try:
        fab.close()
        checked = all(
            bool(np.all(np.asarray(arrays[k]) == float(completed[k])))
            for k in jobs
        )
    finally:
        for cr in crunchers.values():
            cr.dispose()

    done = sum(completed.values())
    lat_ms = sorted(v * 1000.0 for v in latencies)
    return {
        "fabric": fabric,
        "members": members,
        "killed": victim,
        "killed_at_completed": killed_at[0],
        "clients": clients,
        "tenants": tenants,
        "signatures": signatures,
        "requests_target": total_target,
        "completed": done,
        "failed": failed[0],
        "hangs": hangs[0],
        "unnamed_failures": unnamed[0],
        "failure_causes": dict(sorted(failure_causes.items())),
        "rejected": rejected[0],
        "retries_exhausted": retries_exhausted[0],
        "reroutes": int(m_reroutes.value - r0),
        "diversions": int(m_diverted.value - d0),
        "wall_s": round(wall_s, 4),
        "p50_ms": round(_percentile(lat_ms, 0.50), 3),
        "p99_ms": round(_percentile(lat_ms, 0.99), 3),
        "goodput_rps": round(done / wall_s, 2) if wall_s > 0 else None,
        "fused_windows": int(m_windows.value - w0),
        "checked": checked,
        **_run_anatomy(t_wall0),
    }


#: What every multi-process fabric shard runs on, and reports.
FABRIC_WORKER_PLATFORM = "cpu"


def _spawn_fabric_worker(member: str, n: int, local_range: int,
                         max_queue_depth: int = 0,
                         gather_window_ms: float = 4.0,
                         ready_timeout_s: float = 120.0):
    """Spawn one ``tests/_fabric_worker.py`` shard process (the
    _dcn_worker idiom) and block until its READY sentinel."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    # one process per chip: shard workers are children of a process that
    # may hold it, so they are PINNED to the CPU backend — and the merged
    # result says so (``platform``)
    env["JAX_PLATFORMS"] = FABRIC_WORKER_PLATFORM
    proc = subprocess.Popen(
        [sys.executable, os.path.join(repo, "tests", "_fabric_worker.py"),
         str(member), str(int(n)), str(int(local_range)),
         str(int(max_queue_depth)), str(float(gather_window_ms))],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True, env=env, cwd=repo)
    deadline = time.monotonic() + ready_timeout_s
    while True:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"fabric worker {member} died before READY "
                f"(rc={proc.poll()})")
        if line.startswith("FABRIC_READY"):
            return proc
        if time.monotonic() > deadline:
            proc.kill()
            raise RuntimeError(f"fabric worker {member} never came up")


def _worker_rpc(proc, cmd: dict, timeout_s: float = 300.0) -> dict:
    """One JSON command → one JSON reply on a worker's pipes."""
    proc.stdin.write(json.dumps(cmd, allow_nan=False) + "\n")
    proc.stdin.flush()
    line = proc.stdout.readline()
    if not line:
        raise RuntimeError(
            f"fabric worker died mid-command {cmd.get('op')!r} "
            f"(rc={proc.poll()})")
    return json.loads(line)


def run_fabric_mp(
    devices=None,
    fabric: int = 3,
    clients: int = 128,
    tenants: int = 8,
    signatures: int = 4,
    requests_per_client: int = 4,
    n: int = 1 << 13,
    local_range: int = 64,
    max_queue_depth: int = 0,
    gather_window_ms: float | None = None,
) -> dict:
    """The MULTI-PROCESS fabric run (the ``--fabric N`` goodput
    measurement): N shard worker processes (``tests/_fabric_worker.py``
    — each its own interpreter, dispatcher and XLA runtime), the parent
    placing every (tenant, signature) pair on its owning member via the
    SAME pure ``route_decision`` the in-process fabric uses, then all
    shards serving their closed-loop clients CONCURRENTLY.  The merged
    goodput/latency numbers are what the acceptance compares against
    the single-process tier (equal client count, equal pinned-signature
    workload — ``run_loadgen(pin_sig=True)`` — and, when
    ``max_queue_depth`` bounds admission, the SAME per-process queue
    bound on every shard: per-process admission state is exactly what
    sharding scales, so the capacity comparison is one bounded process
    vs N identically-bounded processes)."""
    from cekirdekler_tpu.serve import route_decision

    fabric = max(1, int(fabric))
    clients = max(1, int(clients))
    tenants = max(1, int(tenants))
    signatures = max(1, int(signatures))
    requests = max(1, int(requests_per_client))
    members = [f"m{i}" for i in range(fabric)]

    # the pinned-signature client population: client ci → tenant
    # ci % tenants, signature (ci // tenants) % signatures — each
    # (tenant, signature) pair lands whole on one shard
    combo_clients: dict = {}
    for ci in range(clients):
        key = (ci % tenants, (ci // tenants) % signatures)
        combo_clients[key] = combo_clients.get(key, 0) + 1
    placements: dict = {}
    assignments: dict = {m: [] for m in members}
    for (ti, si), n_clients in sorted(combo_clients.items()):
        sig_key = (f"cid{9100 + si}|lg_inc|{int(n)}x{int(local_range)}+0")
        out = route_decision(f"t{ti}", sig_key, members)
        placements[f"t{ti}/s{si}"] = out["shard"]
        assignments[out["shard"]].append(
            [f"t{ti}", si, n_clients, requests])

    # equal-batch-size normalization: a shard sees ~1/N of the client
    # population, so it gathers ~N× longer than the single tier's 4 ms
    # window to fill the same fused batch per dispatch
    if gather_window_ms is None:
        gather_window_ms = 4.0 * fabric
    procs = {m: _spawn_fabric_worker(m, n, local_range,
                                     max_queue_depth=max_queue_depth,
                                     gather_window_ms=gather_window_ms)
             for m in members}
    merged: dict = {
        "fabric": fabric,
        "members": members,
        "clients": clients,
        "tenants": tenants,
        "signatures": signatures,
        "requests_target": clients * requests,
        "completed": 0, "failed": 0, "hangs": 0,
        "unnamed_failures": 0, "failure_causes": {}, "rejected": 0,
        "checked": True,
        "placements": placements,
    }
    lat_ms: list = []
    try:
        # warm every shard's owned ladder set before the timed section
        for m in members:
            sigs = sorted({si for _, si, _, _ in assignments[m]})
            if sigs:
                _worker_rpc(procs[m], {"op": "warm", "sigs": sigs})
        # serve: one command per shard, replies read concurrently
        replies: dict = {}
        errs: list = []

        def drive(m: str):
            try:
                replies[m] = _worker_rpc(
                    procs[m], {"op": "serve",
                               "assignments": assignments[m]})
            except Exception as e:  # noqa: BLE001 - surfaced below
                errs.append(f"{m}: {e}")

        threads = [threading.Thread(target=drive, args=(m,), daemon=True)
                   for m in members if assignments[m]]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300.0)
        wall_s = time.perf_counter() - t0
        if errs:
            raise RuntimeError("fabric workers failed: " + "; ".join(errs))
        for m, r in replies.items():
            merged["completed"] += r["completed"]
            merged["failed"] += r["failed"]
            merged["hangs"] += r["hangs"]
            merged["unnamed_failures"] += r["unnamed_failures"]
            merged["rejected"] += r["rejected"]
            merged["checked"] = bool(merged["checked"] and r["checked"])
            for k, v in r["failure_causes"].items():
                merged["failure_causes"][k] = \
                    merged["failure_causes"].get(k, 0) + v
            lat_ms.extend(r["latencies_ms"])
    finally:
        for m, p in procs.items():
            try:
                _worker_rpc(p, {"op": "exit"}, timeout_s=10.0)
            except Exception:  # noqa: BLE001 - teardown must proceed
                pass
            try:
                p.wait(timeout=10.0)
            except Exception:  # noqa: BLE001 - teardown must proceed
                p.kill()
    lat_ms.sort()
    merged["wall_s"] = round(wall_s, 4)
    merged["p50_ms"] = round(_percentile(lat_ms, 0.50), 3)
    merged["p99_ms"] = round(_percentile(lat_ms, 0.99), 3)
    merged["goodput_rps"] = (round(merged["completed"] / wall_s, 2)
                             if wall_s > 0 else None)
    merged["failure_causes"] = dict(sorted(merged["failure_causes"].items()))
    merged["platform"] = f"{FABRIC_WORKER_PLATFORM} (pinned shard workers)"
    return merged


def run_fabric_chaos(devices=None, fabric: int = 3, clients: int = 64,
                     tenants: int = 8, signatures: int = 4,
                     requests_per_client: int = 4, n: int = 1 << 13,
                     goodput_floor: float = 0.4, seed: int = 2017) -> dict:
    """The cluster chaos drill (docs/SERVING.md, "Cluster fabric"):
    the fabric workload runs kill-free (the control), then again with
    a seeded mid-run member kill, and the four fabric chaos contracts
    are checked:

    - **no hangs** — every outer future resolves (a preempted shard's
      requests re-route, they never strand);
    - **bit-exact** — every (tenant, signature) array equals its
      completed count exactly (only never-dispatched work re-routes,
      so nothing double-applies);
    - **named failures** — every failure is a framework-named error
      (clean shutdown / typed rejection), never a bare exception;
    - **goodput retained** — killed-run goodput / control goodput
      clears ``goodput_floor``.

    ``checked`` is the conjunction; :func:`fabric_section` reports
    ``fabric_chaos_goodput_frac`` from this, exactness-gated to None on
    any violation (a None is never a pass)."""
    # untimed warmup: ladder compiles are process-global; without it
    # the control run pays them and the killed run does not
    run_fabric(devices, fabric=fabric, clients=4, tenants=tenants,
               signatures=signatures, requests_per_client=1, n=n)
    control = run_fabric(
        devices, fabric=fabric, clients=clients, tenants=tenants,
        signatures=signatures, requests_per_client=requests_per_client,
        n=n)
    killed = run_fabric(
        devices, fabric=fabric, clients=clients, tenants=tenants,
        signatures=signatures, requests_per_client=requests_per_client,
        n=n, kill=True, seed=seed)
    frac = None
    if control.get("goodput_rps") and killed.get("goodput_rps"):
        frac = round(killed["goodput_rps"] / control["goodput_rps"], 4)
    checked = bool(
        control["checked"] and killed["checked"]
        and control["hangs"] == 0 and killed["hangs"] == 0
        and killed["unnamed_failures"] == 0
        and frac is not None and frac >= float(goodput_floor))
    return {
        "fabric": fabric,
        "killed_member": killed["killed"],
        "goodput_frac": frac,
        "goodput_floor": goodput_floor,
        "killed_p99_ms": killed["p99_ms"],
        "hangs": killed["hangs"],
        "failed": killed["failed"],
        "unnamed_failures": killed["unnamed_failures"],
        "failure_causes": killed["failure_causes"],
        "reroutes": killed["reroutes"],
        "checked": checked,
        "control": control,
        "killed": killed,
    }


def fabric_section(devices=None, fabric: int = 3, clients: int = 128,
                   tenants: int = 8, signatures: int = 4,
                   requests_per_client: int = 8, n: int = 1 << 13,
                   max_queue_depth: int = 32,
                   gather_window_ms: float = 1.0) -> dict:
    """The fabric beside its baseline: the SAME pinned-signature
    closed-loop workload against one frontend (the single-process
    baseline) and against an N-process fabric
    (:func:`run_fabric_mp`), plus the in-process kill-and-reroute
    chaos sub-run.  The chaos key is exactness-gated (see
    :func:`run_fabric_chaos`).

    Both tiers run the SAME per-process admission bound
    (``max_queue_depth``): a bounded queue is the per-process state a
    frontend must cap to protect itself, and it is exactly the state
    sharding scales — N shards give the tier N× the admission slots,
    so far fewer requests bounce into the capped retry-sleep loop.
    That is the capacity the fabric adds even on a contended host; the
    1 ms per-shard gather window keeps the bounded batches moving
    rather than idling in the window.

    The goodput comparison needs one core per shard to mean anything:
    N worker processes time-slicing ONE core pay the contention the
    fabric exists to escape, so on such hosts the section records
    ``cpu_limited: true`` alongside the (contention-bound) numbers —
    the chaos fraction and the exactness checks are host-independent
    and stay the gated keys."""
    # untimed warmup (process-global ladder compiles for the baseline;
    # the worker processes warm themselves via the warm op)
    run_loadgen(devices, clients=4, tenants=tenants,
                signatures=signatures, requests_per_client=1,
                mode="closed", n=n)
    single = run_loadgen(
        devices, clients=clients, tenants=tenants, signatures=signatures,
        requests_per_client=requests_per_client, mode="closed", n=n,
        pin_sig=True, max_queue_depth=max_queue_depth)
    fab = run_fabric_mp(
        devices, fabric=fabric, clients=clients, tenants=tenants,
        signatures=signatures, requests_per_client=requests_per_client,
        n=n, max_queue_depth=max_queue_depth,
        gather_window_ms=gather_window_ms)
    chaos = run_fabric_chaos(
        devices, fabric=fabric, clients=max(16, clients // 2),
        tenants=tenants, signatures=signatures,
        requests_per_client=requests_per_client, n=n)
    speedup = None
    if single.get("goodput_rps") and fab.get("goodput_rps"):
        speedup = round(fab["goodput_rps"] / single["goodput_rps"], 3)
    host_cpus = len(os.sched_getaffinity(0)) if hasattr(
        os, "sched_getaffinity") else (os.cpu_count() or 1)
    return {
        "fabric": fabric,
        "clients": clients,
        "host_cpus": host_cpus,
        "cpu_limited": bool(host_cpus < fabric),
        "fabric_goodput_rps": fab["goodput_rps"],
        "fabric_p99_ms": fab["p99_ms"],
        "single_goodput_rps": single["goodput_rps"],
        "single_p99_ms": single["p99_ms"],
        "fabric_goodput_speedup": speedup,
        "fabric_chaos_goodput_frac": (chaos["goodput_frac"]
                                      if chaos["checked"] else None),
        "checked": bool(single["checked"] and fab["checked"]
                        and chaos["checked"]),
        "single": single,
        "sharded": fab,
        "chaos": {k: v for k, v in chaos.items()
                  if k not in ("control", "killed")},
    }


def loadgen_section(devices=None, clients: int = 32, tenants: int = 4,
                    signatures: int = 4, requests_per_client: int = 8,
                    rate_rps: float = 400.0) -> dict:
    """The serving summary: one closed-loop run (the latency
    keys) + one open-loop run (the goodput key) + one chaos sub-run
    (the resilience keys), with the headline floats hoisted to the top
    level.  The chaos keys are exactness-gated: any chaos-contract
    violation (hang, inexact array, unnamed failure, goodput below the
    floor) makes them None, never a pass."""
    closed = run_loadgen(
        devices, clients=clients, tenants=tenants, signatures=signatures,
        requests_per_client=requests_per_client, mode="closed")
    opened = run_loadgen(
        devices, clients=clients, tenants=tenants, signatures=signatures,
        requests_per_client=requests_per_client, mode="open",
        rate_rps=rate_rps)
    chaos = run_chaos(
        devices, clients=clients, tenants=tenants, signatures=signatures,
        requests_per_client=max(2, requests_per_client // 2))
    return {
        "p50_ms": closed["p50_ms"],
        "p99_ms": closed["p99_ms"],
        "goodput_rps": opened["goodput_rps"],
        "coalesce_ratio": closed["coalesce_ratio"],
        "chaos_goodput_frac": (chaos["goodput_frac"]
                               if chaos["checked"] else None),
        "chaos_p99_ms": (chaos["chaos_p99_ms"]
                         if chaos["checked"] else None),
        # the closed run's tail decomposition (obs/reqtrace.py): the
        # p99 queue/device fractions plus the full per-phase anatomy
        "p99_queue_frac": closed["p99_queue_frac"],
        "p99_device_frac": closed["p99_device_frac"],
        "anatomy": closed["anatomy"],
        "coalesced": bool(closed["coalesced"] and opened["coalesced"]),
        "checked": bool(closed["checked"] and opened["checked"]
                        and chaos["checked"]),
        "closed": closed,
        "open": opened,
        "chaos": {k: v for k, v in chaos.items()
                  if k not in ("control", "chaos")},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python tools/loadgen.py",
        description="serving-tier load generator (docs/SERVING.md)")
    ap.add_argument("--clients", type=int, default=32)
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--signatures", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8,
                    help="requests per client")
    ap.add_argument("--mode", choices=("closed", "open", "both", "chaos"),
                    default="closed")
    ap.add_argument("--plan", default=CHAOS_PLAN,
                    help="chaos mode: the seeded CK_FAULTS plan string")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="open-loop aggregate submit rate (rps)")
    ap.add_argument("--n", type=int, default=1 << 14,
                    help="work items per job")
    ap.add_argument("--quota", type=int, default=0,
                    help="per-tenant in-flight quota (0 = generous)")
    ap.add_argument("--fabric", type=int, default=0,
                    help="shard the front-end across N fabric members "
                         "(0 = single frontend); --mode chaos runs the "
                         "seeded kill-and-reroute drill, --mode both "
                         "runs the full single-vs-fabric section")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    if args.fabric > 0:
        if args.mode == "chaos":
            out = run_fabric_chaos(
                fabric=args.fabric, clients=args.clients,
                tenants=args.tenants, signatures=args.signatures,
                requests_per_client=args.requests, n=args.n)
        elif args.mode == "both":
            out = fabric_section(
                fabric=args.fabric, clients=args.clients,
                tenants=args.tenants, signatures=args.signatures,
                requests_per_client=args.requests, n=args.n)
        else:
            out = run_fabric_mp(
                fabric=args.fabric, clients=args.clients,
                tenants=args.tenants, signatures=args.signatures,
                requests_per_client=args.requests, n=args.n)
    elif args.mode == "both":
        out = loadgen_section(
            clients=args.clients, tenants=args.tenants,
            signatures=args.signatures, requests_per_client=args.requests,
            rate_rps=args.rate)
    elif args.mode == "chaos":
        out = run_chaos(
            clients=args.clients, tenants=args.tenants,
            signatures=args.signatures, requests_per_client=args.requests,
            plan=args.plan, n=args.n)
    else:
        out = run_loadgen(
            clients=args.clients, tenants=args.tenants,
            signatures=args.signatures, requests_per_client=args.requests,
            mode=args.mode, rate_rps=args.rate, n=args.n, quota=args.quota)
    if args.json:
        print(json.dumps(_json_safe(out), allow_nan=False))
        return 0
    nested = ("closed", "open", "control", "chaos", "single",
              "sharded", "killed")
    rows = {
        k: v for k, v in out.items()
        if not (k in nested and isinstance(v, dict))
    } if (args.mode in ("both", "chaos") or args.fabric > 0) else out
    rows = {k: v for k, v in rows.items() if k != "anatomy"}
    for k, v in rows.items():
        print(f"  {k:>20}: {v}")
    # the tail-anatomy table rides every human-readable run: top-level
    # when the run carries one, else each nested sub-run's, labeled
    if "anatomy" in out:
        _print_anatomy(out)
    else:
        for name in nested:
            sub = out.get(name)
            if isinstance(sub, dict) and "anatomy" in sub:
                _print_anatomy(sub, label=name)
    if not out.get("checked", True):
        print("  EXACTNESS CHECK FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
