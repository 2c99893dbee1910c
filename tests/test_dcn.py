"""DCN multi-host tier (cluster/dcn.py): real processes x virtual CPU
devices joined through a jax.distributed coordinator, computing one
balanced global range with results exchanged over XLA collectives
(SURVEY.md §7 step 6; VERDICT r4 next-round #4).

Two jobs:
- symmetric 2 processes x 4 devices (the original parity proof);
- ASYMMETRIC 3 processes x (4, 2, 2) devices (VERDICT r5 #6): the
  configuration `_allgather`'s design argument rests on — per-process
  steps differ, the LCM-step table must reflect them, and shares must
  snap to each process's own step.  Skip-guarded for constrained CI via
  ``CK_SKIP_DCN_ASYM=1``.

The in-job assertions (correctness, share agreement, LCM-step table,
balancer movement) live in tests/_dcn_worker.py — this file owns process
lifecycle only.
"""

import os
import socket
import subprocess
import sys

import pytest

#: tests/_dcn_elastic_worker.py's os._exit code for the simulated
#: preemption (tests/ is not a package — the constant is mirrored here).
EXIT_PREEMPTED = 17


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env(n_devices: int) -> dict:
    env = dict(os.environ)
    # production default: x64 OFF — the worker's 64-bit exchange check
    # must run against real canonicalization, not the rig's x64 override
    env.pop("JAX_ENABLE_X64", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    return env


def _run_job(counts: list[int], timeout: float = 240.0) -> None:
    here = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(here, "_dcn_worker.py")
    port = _free_port()
    nproc = len(counts)
    counts_arg = ",".join(str(c) for c in counts)
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(pid), str(nproc), str(port),
             counts_arg],
            env=_worker_env(counts[pid]), cwd=os.path.dirname(here),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for pid in range(nproc)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out[-3000:]}"
        assert f"DCN_OK pid={pid}" in out, out[-3000:]


def test_two_process_distributed_compute():
    _run_job([4, 4])


@pytest.mark.skipif(
    os.environ.get("CK_SKIP_DCN_ASYM") == "1",
    reason="asymmetric DCN job disabled (CK_SKIP_DCN_ASYM=1)",
)
def test_asymmetric_three_process_distributed_compute():
    """4+2+2 virtual devices across 3 processes (VERDICT r5 #6): unequal
    per-process steps through the same SPMD balancer — the share table,
    LCM-step table, and exchange must all hold without the symmetric
    reshape `multihost_utils.process_allgather` would need."""
    _run_job([4, 2, 2])


# ---------------------------------------------------------------------------
# kill-and-rejoin (ISSUE 13): preemption-safe elastic resume
# ---------------------------------------------------------------------------

def _run_elastic_job(counts, ckpt_root, phase, windows, kill_after,
                     decision_dir, expect_rc=0, expect_ok=True,
                     timeout=240.0):
    here = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(here, "_dcn_elastic_worker.py")
    port = _free_port()
    nproc = len(counts)
    counts_arg = ",".join(str(c) for c in counts)
    procs = []
    for pid in range(nproc):
        env = _worker_env(counts[pid])
        env["CK_DECISION_LOG"] = decision_dir + os.sep
        procs.append(subprocess.Popen(
            [sys.executable, worker, str(pid), str(nproc), str(port),
             counts_arg, ckpt_root, phase, str(windows), str(kill_after)],
            env=env, cwd=os.path.dirname(here),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        ))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == expect_rc, \
            f"worker {pid} rc={p.returncode} (want {expect_rc}):\n{out[-3000:]}"
        if expect_ok:
            assert f"DCN_ELASTIC_OK pid={pid}" in out, out[-3000:]
    return outs


@pytest.mark.skipif(
    os.environ.get("CK_SKIP_DCN_ELASTIC") == "1",
    reason="elastic DCN job disabled (CK_SKIP_DCN_ELASTIC=1)",
)
def test_kill_and_rejoin_converges_bit_identical(tmp_path):
    """The ISSUE 13 acceptance harness: a 2x2-device DCN job is
    preempted (every process os._exit's with no cleanup) after window
    3 of 6, a TORN newest checkpoint is planted, and a NEW job with a
    DIFFERENT membership (2+1 devices — one process resized, so
    member-leave/member-join re-splits are recorded) resumes from the
    last complete window and finishes.  The worker asserts the final
    image is bit-identical to the undisturbed run's and that the
    spilled decision log — membership transitions and checkpoint
    restore included — replays green through verify_records."""
    ckpt_root = str(tmp_path / "ckpt")
    decisions = str(tmp_path / "decisions")
    os.makedirs(decisions, exist_ok=True)
    windows, kill_after = 6, 3
    # phase 1: run + die mid-job (preemption — rc is the _exit code)
    _run_elastic_job([2, 2], ckpt_root, "first", windows, kill_after,
                     decisions, expect_rc=EXIT_PREEMPTED, expect_ok=False)
    # the checkpoints the preempted run left are complete through
    # kill_after (atomic rename — no half-windows)
    steps = sorted(os.listdir(ckpt_root))
    assert f"step_{kill_after:012d}" in steps, steps
    # plant a TORN newest step: the resume must fall back past it
    torn = os.path.join(ckpt_root, f"step_{kill_after + 1:012d}")
    os.makedirs(torn, exist_ok=True)
    with open(os.path.join(torn, "arrays.npz"), "wb") as f:
        f.write(b"definitely not a zip file")
    # phase 2: rejoin with a CHANGED membership (2+1 devices)
    outs = _run_elastic_job([2, 1], ckpt_root, "rejoin", windows,
                            kill_after, decisions)
    assert any("DCN_ELASTIC_REPLAY pid=0 ok=True" in o for o in outs), \
        outs[0][-2000:]
