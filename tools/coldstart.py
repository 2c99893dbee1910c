#!/usr/bin/env python
"""Cold-start vs cache-warm first-call latency: a standalone CLI over
:func:`coldstart_section` (ISSUE 18).

Three SUBPROCESS incarnations per workload, each a fresh interpreter
(process-cold is a process property — it cannot be measured in-process):

- **cold** — no ``CK_COMPILE_CACHE`` and jax's persistent compilation
  cache switched OFF explicitly (``JAX_ENABLE_COMPILATION_CACHE=0`` — the
  package otherwise places one in the checkout): the autoscale worst
  case.  Times the first fused batch (compile + execute) and a
  steady-state batch.
- **populate** — same run with the manifest armed and jax's cache placed
  from outside (``JAX_COMPILATION_CACHE_DIR=<root>/xla``): the
  engage-time recorder (``core/compilecache.record_engaged``) persists
  the window spec and jax's persistent cache captures the XLA
  executables.  This is the PRODUCTION population flow, not a synthetic
  writer.
- **warm** — same placement, ``warm_from_disk`` precompiles the full
  predicated launch ladder BEFORE traffic, then times the same first
  batch.  ``cold_start_warm_speedup = cold.first / warm.first`` is the
  headline (higher is better).

Every incarnation is a child PINNED to the CPU backend
(``JAX_PLATFORMS=cpu``) and says so in its row: a chip belongs to one
process, and a parent that has touched jax holds it.  The numbers are
host-CPU compile walls, never device metrics.

Exactness gate: all three incarnations hash their result arrays —
the cache must be bit-invisible (``exact`` is False otherwise, and the
speedup is withheld).  A ``resilience_section`` result handed in
contributes its ``rejoin_converge_iters``, so the two autoscale numbers
(rejoin convergence, rejoin compile cost) are read side by side.

Workloads: the n-body ladder (``workloads.NBODY_SRC`` through
``compute_fused_batch`` — the serving tier's coalesced entry) is the
headline; the flash-attention ladder rides the XLA persistent cache +
file-backed ``BlockTuner`` profile (same tuned blocks => same
executable => disk hit) and is reported as a secondary block.

Usage::

    python tools/coldstart.py [--n 4096] [--iters 4] [--json]
    python tools/coldstart.py --child warm --workload nbody --cache DIR
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # standalone `python tools/coldstart.py`
    sys.path.insert(0, REPO)

CACHE_ENV = "CK_COMPILE_CACHE"  # mirrored from core/compilecache (child
#                                 sets env BEFORE the package import)

CHILD_TIMEOUT_S = 240.0
_CID = 9001  # fixed compute id: all incarnations coalesce identically


# ---------------------------------------------------------------- children


def _digest(*arrays) -> str:
    import numpy as np

    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


def _child_nbody(args, out: dict) -> dict:
    """One incarnation of the n-body fused-batch ladder."""
    import numpy as np

    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core import NumberCruncher
    from cekirdekler_tpu.hardware import platforms
    from cekirdekler_tpu.workloads import NBODY_SRC

    n, lr, dt = args.n, args.local_range, 0.0001
    rng = np.random.default_rng(42)
    pos = (rng.random((3, n), dtype=np.float32) - 0.5) * 2.0
    x = ClArray(pos[0].copy(), name="x", read_only=True)
    y = ClArray(pos[1].copy(), name="y", read_only=True)
    z = ClArray(pos[2].copy(), name="z", read_only=True)
    vel = [ClArray(n, np.float32, name=f"v{c}", partial_read=True)
           for c in "xyz"]
    cr = NumberCruncher(platforms().cpus().subset(1), NBODY_SRC)
    params = [x, y, z, *vel]
    vals = {"nBody": (n, dt)}
    try:
        if args.child == "warm":
            from cekirdekler_tpu.core.compilecache import warm_from_disk

            t0 = time.perf_counter()
            out["warm"] = warm_from_disk(cr.cores)
            out["warmup_s"] = round(time.perf_counter() - t0, 4)
        cr.enqueue_mode = True

        def batch() -> float:
            t0 = time.perf_counter()
            cr.cores.compute_fused_batch(
                ["nBody"], params, _CID, n, lr, args.iters,
                value_args=vals)
            cr.barrier()
            return round(time.perf_counter() - t0, 4)

        out["first_batch_s"] = batch()
        out["steady_batch_s"] = batch()
        cr.enqueue_mode = False  # flush deferred readbacks
        out["digest"] = _digest(*(np.asarray(v) for v in vel))
        out["fused_compiles"] = cr.cores.program.fused_compiled_count
        out["call_compiles"] = cr.cores.program.compiled_count
    finally:
        cr.dispose()
    return out


def _child_flash(args, out: dict) -> dict:
    """One incarnation of the flash-attention ladder.  No manifest spec
    (pure jax path) — ``warm`` differs from ``populate`` only in that
    the XLA persistent cache and the BlockTuner's profile store are
    already populated, which is exactly the production rejoin state."""
    import numpy as np

    from cekirdekler_tpu.core.compilecache import CACHE
    from cekirdekler_tpu.ops.flash_attention import flash_attention

    if CACHE.enabled:
        CACHE.arm()
    import jax.numpy as jnp

    rng = np.random.default_rng(42)
    shape = (1, args.seq, 1, 64)
    q, k, v = (jnp.asarray(rng.standard_normal(shape).astype(np.float32))
               for _ in range(3))
    t0 = time.perf_counter()
    o = flash_attention(q, k, v)
    o.block_until_ready()
    out["first_batch_s"] = round(time.perf_counter() - t0, 4)
    t0 = time.perf_counter()
    o2 = flash_attention(q, k, v)
    o2.block_until_ready()
    out["steady_batch_s"] = round(time.perf_counter() - t0, 4)
    out["digest"] = _digest(np.asarray(o))
    return out


def _child(args) -> int:
    """Run one incarnation; print exactly one JSON line on stdout."""
    if args.cache:
        os.environ[CACHE_ENV] = args.cache
    else:
        os.environ.pop(CACHE_ENV, None)
    out: dict = {"mode": args.child, "workload": args.workload,
                 "cache": bool(args.cache), "pid": os.getpid(),
                 "platform": os.environ.get("JAX_PLATFORMS", "")}
    try:
        if args.workload == "flash":
            out = _child_flash(args, out)
        else:
            out = _child_nbody(args, out)
    except Exception as exc:  # a child crash is DATA for the parent
        out["error"] = f"{type(exc).__name__}: {exc}"
        print(json.dumps(out, allow_nan=False))
        return 1
    print(json.dumps(out, allow_nan=False))
    return 0


# ------------------------------------------------------------------ parent


def _spawn(mode: str, workload: str, cache: str, n: int, local_range: int,
           iters: int, seq: int, timeout: float = CHILD_TIMEOUT_S) -> dict:
    env = os.environ.copy()
    env.pop(CACHE_ENV, None)  # the child's --cache flag is authoritative
    # one process per chip: the parent may hold it, so every incarnation
    # is pinned to the CPU backend (and reports that as its platform)
    env["JAX_PLATFORMS"] = "cpu"
    if cache:
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache, "xla")
        env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    else:
        # process-cold means NO persistent cache, stated — not the
        # accident of nobody having placed one
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env["JAX_ENABLE_COMPILATION_CACHE"] = "0"
    cmd = [sys.executable, os.path.abspath(__file__),
           "--child", mode, "--workload", workload, "--cache", cache,
           "--n", str(n), "--local-range", str(local_range),
           "--iters", str(iters), "--seq", str(seq)]
    try:
        proc = subprocess.run(cmd, env=env, capture_output=True,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"error": f"timeout after {timeout}s", "mode": mode}
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except (json.JSONDecodeError, ValueError):
            continue
    return {"error": f"no JSON from child (rc={proc.returncode}): "
                     f"{proc.stderr.strip()[-400:]}", "mode": mode}


def _trio(workload: str, root: str, n: int, local_range: int, iters: int,
          seq: int) -> dict:
    """cold -> populate -> warm for one workload over a shared cache
    root; returns the three children plus derived speedup/exactness."""
    cache = os.path.join(root, workload)
    os.makedirs(cache, exist_ok=True)
    kw = dict(workload=workload, n=n, local_range=local_range,
              iters=iters, seq=seq)
    cold = _spawn("cold", cache="", **kw)
    populate = _spawn("populate", cache=cache, **kw)
    warm = _spawn("warm", cache=cache, **kw)
    out = {"cold": cold, "populate": populate, "warm": warm}
    digests = [c.get("digest") for c in (cold, populate, warm)]
    out["exact"] = (None not in digests and len(set(digests)) == 1)
    cold_s, warm_s = cold.get("first_batch_s"), warm.get("first_batch_s")
    if out["exact"] and cold_s and warm_s:
        out["warm_speedup"] = round(cold_s / warm_s, 3)
        out["cold_first_batch_s"] = cold_s
        out["warm_first_batch_s"] = warm_s
        out["warmup_s"] = warm.get("warmup_s")
    else:
        out["warm_speedup"] = None
    return out


def coldstart_section(devices=None, resilience=None, n: int = 4096,
                      local_range: int = 256, iters: int = 4,
                      seq: int = 256, include_flash: bool = True,
                      cache_root: str | None = None) -> dict:
    """Process-cold vs cache-warm first-call latency for the n-body
    (headline) and flash ladders.

    ``devices`` is accepted and ignored: the measurements are
    subprocess-scoped — a fresh interpreter per incarnation is the
    point.  ``resilience`` (``tools/resilience.resilience_section``'s
    result dict) contributes ``rejoin_converge_iters`` to the same
    report."""
    del devices  # children own their device discovery
    root = cache_root or tempfile.mkdtemp(prefix="ck_coldstart_")
    own_root = cache_root is None
    try:
        nbody = _trio("nbody", root, n, local_range, iters, seq)
        flash = (_trio("flash", root, n, local_range, iters, seq)
                 if include_flash else {"skipped": "disabled"})
    finally:
        if own_root:
            shutil.rmtree(root, ignore_errors=True)
    out = {
        "platform": "cpu (pinned children; host compile walls)",
        # the headline: n-body only — the flash path's speedup is
        # tuner/interpret-mode dependent and reported beside it
        "cold_start_warm_speedup": nbody.get("warm_speedup"),
        "rejoin_converge_iters": (
            resilience.get("rejoin_converge_iters")
            if isinstance(resilience, dict) else None),
        "exact": bool(nbody.get("exact")),
        "nbody": nbody,
        "flash": flash,
    }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python tools/coldstart.py",
        description="process-cold vs cache-warm first-call latency "
                    "(persistent executable cache, docs/PARALLELISM.md)")
    ap.add_argument("--child", default=None,
                    choices=("cold", "populate", "warm"),
                    help=argparse.SUPPRESS)  # internal: one incarnation
    ap.add_argument("--workload", default="nbody",
                    choices=("nbody", "flash"))
    ap.add_argument("--cache", default="",
                    help=argparse.SUPPRESS)  # internal: child cache root
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--local-range", type=int, default=256)
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--no-flash", action="store_true")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    if args.child:
        return _child(args)
    out = coldstart_section(
        n=args.n, local_range=args.local_range, iters=args.iters,
        seq=args.seq, include_flash=not args.no_flash)
    if args.json:
        print(json.dumps(out, indent=1, sort_keys=True, default=str,
                         allow_nan=False))
    else:
        nb = out["nbody"]
        print(f"cold_start_warm_speedup = {out['cold_start_warm_speedup']}")
        print(f"cold first batch        = {nb.get('cold_first_batch_s')}s")
        print(f"warm first batch        = {nb.get('warm_first_batch_s')}s "
              f"(+{nb.get('warmup_s')}s AOT warmup)")
        print(f"exact                   = {out['exact']}")
    if not out["exact"]:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
