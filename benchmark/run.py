#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run: data from the seed, the cell's own shapes warmed up
(set-up), then a closed loop of calls for ``--seconds`` seconds, then the
check of what the window produced against the configuration's plain
reference.  The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (traced,
``breakdown`` too) and last ``compared``, each number compared beside its
limit, which are also the last lines of standard error.  ``--trace 0``
reports the cell's end-to-end metrics; ``--trace 1`` runs a short window
under the profiler and reports its per-layer metrics.
The process fixes glibc's allocator thresholds before it allocates anything
large (``steady_allocator``), so that what a run reads does not hang on the
order of the process's first frees.

Fails, printing no result, without the program beside it (exit 2) or without
as many TPU chips as the cell asks for (exit 3): there is no CPU fallback.
What belongs to one cell lives in files found by name — see cells.py.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import ctypes  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import cells  # noqa: E402
import xplane  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_DIR = os.path.join(ROOT, ".bench_trace")

# glibc's allocator as a long-running service sets it, before anything large
# is allocated: blocks up to 32 MiB come from the heap (M_MMAP_THRESHOLD, its
# largest value) and the heap's top is not given back (M_TRIM_THRESHOLD,
# M_TOP_PAD).  Left to its defaults the allocator moves both thresholds by
# the order in which a process happened to free its first large blocks, and a
# process whose read-back buffers (4 MiB each, allocated anew by every
# ``copy_to_host_async``) lie at the heap's top maps and faults them in again
# at every call, from its first window to its last: PERF.md section 2.
MALLOPT = {-3: 32 << 20, -1: 1 << 30, -2: 64 << 20}


def steady_allocator() -> bool:
    """Fix glibc's thresholds (``MALLOPT``); a caller who set one through the
    environment (``MALLOC_*_``, ``GLIBC_TUNABLES``) keeps that choice, and
    another C library is left alone."""
    if any(k.startswith("MALLOC_") and k.endswith("_") for k in os.environ) \
            or "glibc.malloc" in os.environ.get("GLIBC_TUNABLES", ""):
        return False
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    return all(mallopt(param, value) == 1 for param, value in MALLOPT.items())


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class CompileCounter:
    """Counts XLA backend compiles (cache reads included) as jax reports
    them; the window asks how many fell inside it."""

    def __init__(self):
        self.n = 0
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.n += 1


def build(cell: cells.Cell, seed: int, devices, trace: bool):
    """Set-up up to the warmed program: host data from the seed, arrays with
    the configuration's flags, the cruncher with its pins."""
    import jax
    import numpy as np

    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core import cores as ck_cores
    from cekirdekler_tpu.core.cruncher import NumberCruncher

    cfg, params = cell.cfg, cell.params
    n, lr = int(params["n"]), int(cfg["local_range"])
    if "width" in cfg and int(cfg["width"]) * int(cfg["height"]) != n:
        raise ValueError(f"{cell.name}: n {n} is not the configuration's "
                         f"{cfg['width']} x {cfg['height']} frame")
    data, values = cell.ref.inputs(cfg, params,
                                   np.random.default_rng(int(seed)))
    arrays = {}
    for spec in cfg["arrays"]:
        name, host = spec["name"], data[spec["name"]]
        if spec.get("fast"):
            # the native page-aligned allocation; writing the data touches
            # every page now, not inside the window
            arr = ClArray(host.size, host.dtype, name=name, fast=True,
                          **spec["flags"])
            arr.host()[:] = host
            data[name] = arr.host()
        else:
            arr = ClArray(host, name=name, **spec["flags"])
        arrays[name] = arr
    first, *rest = arrays.values()
    group = first.next_param(*rest)
    cr = NumberCruncher(devices.subset(int(cfg["lanes"])),
                        cells.kernel_source(cfg))
    # a configuration or a traffic file may set the cruncher's public
    # properties, by name, and nothing else of it
    for knob, value in {**cfg.get("cruncher", {}),
                        **params.get("pins", {})}.items():
        if not isinstance(getattr(NumberCruncher, knob, None), property):
            raise KeyError(f"{cell.name}: {knob!r} is not a public property "
                           "of NumberCruncher")
        setattr(cr, knob, value)
    cid, kernel = 2300, cfg["kernel"]
    # further arguments of compute() a configuration asks for (pipeline=True,
    # pipeline_blobs=8, ...); the engines' constants are given by name
    extra = {k: getattr(ck_cores, v) if isinstance(v, str) else v
             for k, v in cfg.get("compute_kwargs", {}).items()}

    # a configuration may give the calls scalar arguments of their own: a
    # cycle for the window's calls and one set apart, used by the last
    # warm-up call and by the fresh call, so that what the window leaves
    # cannot have been left by warm-up
    plan = (cell.ref.call_values(cfg, params, values)
            if hasattr(cell.ref, "call_values") else {})
    ctx = SimpleNamespace(
        cell=cell, cfg=cfg, params=params, seed=int(seed), cr=cr, cid=cid,
        arrays=arrays, data=data, values=values, n=n,
        cycle=[tuple(v) for v in plan.get("cycle", [values])],
        apart=tuple(plan["apart"]) if "apart" in plan else None,
        span=(jax.profiler.TraceAnnotation if trace
              else (lambda _name: contextlib.nullcontext())),
        iterations=0, walls=[], ranges_log=[], window_compiles=0, wall=0.0,
        reduced=None, device_kind=jax.devices()[0].device_kind)

    def compute() -> None:
        group.compute(cr, cid, kernel, n, lr, values=ctx.values, **extra)

    ctx.compute = compute
    return ctx


def warm_up(ctx, compiles: CompileCounter) -> None:
    """The cell's own shapes and no others: one synchronous compute (first
    upload, the per-call executable), then ``warmup_calls`` calls of the timed
    loop itself, through the cycle of the calls' scalar arguments.  A balanced
    cell's ranges never stop moving (the balancer answers every barrier), and
    a new split can ask for a ladder rung not compiled yet; its traffic file
    therefore asks for ``warmup_quiet``: keep calling until that many calls
    in a row compiled nothing.  Where the configuration sets arguments
    ``apart``, the last warm-up call runs with them: the outputs then hold
    what no call of the window writes."""
    loop, params = ctx.cell.loop, ctx.params
    per_call = int(params["iterations_per_call"])
    ctx.values = ctx.cycle[0]
    ctx.compute()
    ctx.iterations += 1
    loop.enter(ctx)
    ctx.call = loop.make_call(ctx)
    quiet_for = params.get("warmup_quiet")
    calls = quiet = 0
    while True:
        c0 = compiles.n
        ctx.values = ctx.cycle[calls % len(ctx.cycle)]
        ctx.call()
        ctx.iterations += per_call
        calls += 1
        quiet = quiet + 1 if compiles.n == c0 else 0
        if calls < max(int(params["warmup_calls"]), len(ctx.cycle)):
            continue
        if quiet_for is None or quiet >= int(quiet_for["calls"]):
            break
        if calls >= int(quiet_for["max_calls"]):
            log(f"warm-up: still compiling after {calls} calls; the window "
                "starts anyway")
            break
    if ctx.apart is not None:
        ctx.values = ctx.apart
        ctx.call()
        ctx.iterations += per_call
        calls += 1
    log(f"warm-up: {calls} calls, ranges {ctx.cr.ranges_of(ctx.cid)}")


def window(ctx, seconds: float, compiles: CompileCounter) -> None:
    """The measured closed loop: call after call until ``seconds`` have
    passed; every call ends synchronised, so the last one is the fence.
    ``ctx.values`` is left at the last call's arguments."""
    call, cr, cid, span = ctx.call, ctx.cr, ctx.cid, ctx.span
    walls, ranges_log, cycle = ctx.walls, ctx.ranges_log, ctx.cycle
    ranges_log.append(cr.ranges_of(cid))
    gc.collect()
    c0 = compiles.n
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        ctx.values = cycle[len(walls) % len(cycle)]
        tc = time.perf_counter()
        with span("bench/call"):
            call()
        te = time.perf_counter()
        walls.append(te - tc)
        ranges_log.append(cr.ranges_of(cid))
        if te >= deadline:
            break
    ctx.wall = te - t0
    ctx.window_compiles = compiles.n - c0
    ctx.iterations += len(walls) * int(ctx.params["iterations_per_call"])


def read_back(ctx) -> dict:
    """After the window has closed: flush, and hand the reference what the
    timed path left in the host arrays, with the arguments of the window's
    last call and the lanes' ranges after every call.  Then the
    configuration's ``fresh_call``: the host arrays of one role are filled
    anew (state zeroed, an output poisoned), and one more call of the same
    compiled programs runs, with the arguments set apart where there are
    any.  It shows what one call writes, whatever the window or warm-up had
    left there, and does not depend on how many iterations the window held."""
    loop, cfg = ctx.cell.loop, ctx.cfg
    loop.leave(ctx)
    roles = {s["name"]: s["role"] for s in cfg["arrays"]}
    outs = [k for k, r in roles.items() if r in ("state", "output")]
    observed = {"iterations": ctx.iterations, "values": ctx.values,
                "ranges_log": ctx.ranges_log, "fresh": None,
                "outputs": {k: ctx.arrays[k].host() for k in outs}}
    fresh = cfg.get("fresh_call")
    if fresh:
        observed["outputs"] = {k: v.copy()
                               for k, v in observed["outputs"].items()}
        for k, r in roles.items():
            if r == fresh["fill_role"]:
                ctx.arrays[k].host()[:] = fresh["fill_value"]
        if ctx.apart is not None:
            ctx.values = ctx.apart
        iterations = int(ctx.params["iterations_per_call"])
        if fresh["upload"]:
            ctx.compute()  # synchronous: uploads the re-filled arrays
            iterations += 1
        loop.enter(ctx)
        ctx.call()
        loop.leave(ctx)
        observed["fresh"] = {
            "iterations": iterations, "values": ctx.values,
            "outputs": {k: ctx.arrays[k].host() for k in outs}}
    return observed


def device_info(devices_used) -> dict:
    import jax

    d0 = jax.devices()[0]
    peak = 0
    for d in devices_used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def quantile(ordered: list, q: float) -> float:
    return ordered[min(len(ordered) - 1, int(q * len(ordered)))]


def end_to_end(ctx, setup_s: float) -> dict:
    walls = sorted(ctx.walls)
    items = len(walls) * ctx.cell.loop.items_per_call(ctx.params)
    return {"items_per_s": items / ctx.wall / 1e6,
            "call_p50_ms": statistics.median(walls) * 1e3,
            "call_p95_ms": quantile(walls, 0.95) * 1e3,
            "setup_s": setup_s}


def wall_profile(walls: list) -> dict:
    """A few order statistics of the call walls for the log: enough to tell
    a run that was slower throughout from one with a stretch of slow calls."""
    w = sorted(walls)
    half = max(len(walls) // 2, 1)
    return {"p05": quantile(w, 0.05), "p50": quantile(w, 0.5),
            "p95": quantile(w, 0.95), "max": w[-1],
            "mean_first_half": statistics.fmean(walls[:half]),
            "mean_second_half": statistics.fmean(walls[half:] or walls)}


def slow_calls(walls: list) -> int:
    """Calls that took over one and a half times the median: the host's
    stalls, which move a rate and leave the median where it was."""
    limit = 1.5 * statistics.median(walls)
    return sum(w > limit for w in walls)


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             devices, compared_out: list | None = None) -> dict:
    """Everything of a run but the look for a chip: returns the result
    object.  ``devices`` is the program's device selection to take the
    cell's lanes from; ``compared_out`` receives the numbers compared as
    ``Compared`` tuples (the result carries them under ``compared``)."""
    import jax

    compiles = CompileCounter()
    ctx = build(cell, seed, devices, trace)
    used = [w.device for w in ctx.cr.cores.workers]
    readers = {}
    try:
        if trace:
            for m in cell.per_layer:
                readers[m["name"]] = cells.load_reader(m["name"])
            seconds = min(seconds, float(cell.params["trace_seconds"]))
        warm_up(ctx, compiles)
        setup_s = time.perf_counter() - T_PROCESS
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host spans come from annotations
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            try:
                window(ctx, seconds, compiles)
            finally:
                jax.profiler.stop_trace()
        else:
            window(ctx, seconds, compiles)
        log(f"window: {len(ctx.walls)} calls in {ctx.wall:.3f} s, "
            f"{ctx.window_compiles} compiles inside it; call walls ms "
            + " ".join(f"{k}={v * 1e3:.2f}" for k, v in wall_profile(
                ctx.walls).items())
            + f"; {slow_calls(ctx.walls)} calls over 1.5 x the median")
        observed = read_back(ctx)
        device = device_info(used)
        t_ref = time.perf_counter()
        compared = cell.ref.compare(cell.cfg, cell.params, ctx.data,
                                    ctx.values, observed, ctx.seed)
        for c in compared:
            log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
                f"{'ok' if c.ok else 'NOT CORRECT'}")
        log(f"reference and comparison took "
            f"{time.perf_counter() - t_ref:.2f} s (not in setup_s)")
        failed = int(ctx.cr.number_of_errors_happened)
        if compared_out is not None:
            compared_out[:] = compared
        result = {"correct": bool(compared) and all(c.ok for c in compared)
                  and failed == 0,
                  "attempted": len(ctx.walls), "failed": failed}
        if trace:
            ctx.reduced = xplane.reduce(
                xplane.load(xplane.find_xplane(TRACE_DIR)))
            ctx.peaks = cells.peaks(ctx.device_kind)
            metrics = {}
            for m in cell.per_layer:
                value = readers[m["name"]].read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
            busy = ctx.reduced.busy_s
            device["busy_s"] = sum(busy.values()) / len(busy)
            device["window_s"] = ctx.reduced.window_s
            result["breakdown"] = xplane.breakdown(ctx.reduced)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        else:
            values = end_to_end(ctx, setup_s)
            metrics = {m["name"]: {"value": values[cells.quantity(m["name"])],
                                   "unit": m["unit"]}
                       for m in cell.end_to_end}
        result["metrics"] = metrics
        result["device"] = device
        # last in the line: each number compared beside its limit (a check
        # that produced no number reads null)
        result["compared"] = {
            c.name: {"value": c.value if math.isfinite(c.value) else None,
                     "limit": c.limit} for c in compared}
        return result
    finally:
        ctx.cr.dispose()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    steady = steady_allocator()

    # jax's persistent cache at a fixed place inside the checkout (the path
    # is part of its key); the program honours the variable and sets no other
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    try:
        cell = cells.load_cell(args.workload)
        import jax

        import cekirdekler_tpu as ct
    except (ImportError, OSError, KeyError) as e:
        print(f"benchmark: cannot run here ({type(e).__name__}: {e}); run "
              "from the root of a checkout of the program", file=sys.stderr)
        return 2
    tpus = [d for d in jax.devices() if d.platform == "tpu"]
    if len(tpus) < cell.chips:
        print(f"benchmark: {args.workload} needs {cell.chips} TPU chip(s), "
              f"jax sees {[str(d) for d in jax.devices()]}; there is no CPU "
              "fallback", file=sys.stderr)
        return 3
    log(f"{args.workload} seed {args.seed} seconds {args.seconds} trace "
        f"{args.trace} on {len(tpus)} x {tpus[0].device_kind}; compile cache "
        f"{jax.config.jax_compilation_cache_dir}; allocator "
        f"{'fixed' if steady else 'as the environment or the library has it'}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      ct.all_devices().tpus())
    print(json.dumps(result, allow_nan=False), flush=True)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
