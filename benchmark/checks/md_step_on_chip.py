#!/usr/bin/env python3
"""ISSUE 50's step 6 on the chip, in one process: ONE synchronous ``compute()``
of ``compute_lj_force`` at the configuration's own size (the rule that sets the
scale: at most 2.0 s), the same kernel written component by component beside
it (``fixtures/shoc_md_components.cl``: what a port had to write before the
kernel language had vector types), their forces compared byte for byte and
against the plain reference on a sample, and a sweep of the public
``stream_chunks`` property.

    python3 benchmark/checks/md_step_on_chip.py --seed 2300000001 \
        [--atoms 1048576 --lattice 128 128 64] [--pin 1] \
        [--chunks 1 2 4 8 16 32 64] [--skip-components]

Needs the chip: it exits 3 without one, as ``control_on_chip.py`` does (the
CPU-size checks are ``test_md_cell.py`` and ``tests/test_vector_types.py``).
Prints one JSON line a measurement.  Times are the host's clock around
``compute()`` (it returns with the forces in the caller's array); nothing here
is a cell of the benchmark.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]


def say(**row) -> None:
    print(json.dumps(row), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=2_300_000_001)
    ap.add_argument("--atoms", type=int, default=None)
    ap.add_argument("--lattice", type=int, nargs=3, default=None)
    ap.add_argument("--computes", type=int, default=3)
    ap.add_argument("--pin", type=int, default=0,
                    help="stream_chunks of the first computes (0: tuned)")
    ap.add_argument("--chunks", type=int, nargs="*", default=[])
    ap.add_argument("--skip-components", action="store_true")
    args = ap.parse_args(argv)

    import numpy as np

    import cells

    import jax

    import cekirdekler_tpu as ct
    from cekirdekler_tpu import ClArray
    from cekirdekler_tpu.core.cruncher import NumberCruncher
    from cekirdekler_tpu.kernel.registry import lowering_meta

    cell = cells.load_cell("md_lj_step_1chip")
    cfg, params, ref = dict(cell.cfg), dict(cell.params), cell.ref
    if args.atoms:
        cfg.update(atoms=args.atoms, lattice=args.lattice)
        params["n"] = args.atoms
    if not any(d.platform == "tpu" for d in jax.devices()):
        # every number this prints is a device number: the CPU-size checks
        # live in test_md_cell.py and tests/test_vector_types.py
        print("needs 1 TPU chip", file=sys.stderr)
        return 3
    devices = ct.all_devices().tpus()
    n, lr, kernel = int(cfg["atoms"]), int(cfg["local_range"]), cfg["kernel"]
    t0 = time.perf_counter()
    data, values = ref.inputs(cfg, params, np.random.default_rng(args.seed))
    plan = ref.call_values(cfg, params, values)
    say(what="inputs", atoms=n, seconds=time.perf_counter() - t0,
        device=str(jax.devices()[0].device_kind))
    frames = data["frames"]
    with open(os.path.join(HERE, "fixtures", "shoc_md_components.cl")) as f:
        components = f.read()
    sources = [("vector", cells.kernel_source(cfg))]
    if not args.skip_components:
        sources.append(("components", components))
    forces = {}
    for label, source in sources:
        arrays = {s["name"]: ClArray(data[s["name"]].copy() if s["name"] != "neighList"
                                     else data[s["name"]], name=s["name"], **s["flags"])
                  for s in cfg["arrays"]}
        first, *rest = arrays.values()
        group = first.next_param(*rest)
        cr = NumberCruncher(devices.subset(1), source)
        cr.stream_chunks = args.pin  # 0: the tuner free, as the cell runs
        w = cr.cores.workers[0]
        try:
            def step(k: int) -> float:
                arrays["position"].host()[:] = frames[k]
                t = time.perf_counter()
                group.compute(cr, 4800, kernel, n, lr,
                              values=tuple(plan["cycle"][k]))
                return time.perf_counter() - t

            cold = step(0)
            arrays["neighList"].read = False
            walls = [step((i + 1) % 4) for i in range(args.computes)]
            last = args.computes % 4
            info = cr.cores.program.launcher(
                kernel, n, lr, n, platform=w.device.platform)[1]
            meta = lowering_meta([info])
            forces[label] = arrays["force3"].host().copy()
            say(what="compute", kernel=label, cold_s=cold, walls_s=walls,
                errors=int(cr.number_of_errors_happened), pin=args.pin,
                chunks_used=max(cr.cores.last_stream_chunks.values(),
                                default=None),
                **{k: meta.get(k) for k in ("lowering", "veto", "loops", "access",
                                            "vector", "scatter", "keys", "views")})
            if label == "vector":
                sample = np.sort(np.random.default_rng(args.seed).choice(
                    n, min(n, 16384), replace=False))
                _k, cutsq, lj1, lj2, _n = plan["cycle"][last]
                want = ref.forces(frames[last].reshape(n, 4),
                                  data["neighList"].reshape(-1, n), sample,
                                  cutsq, lj1, lj2)
                got = forces[label].reshape(n, 4)
                say(what="against the reference", atoms=len(sample),
                    rel_err=float(np.abs(got[sample, :3] - want).max()
                                  / np.abs(want).max()),
                    w_nonzero=int((got[:, 3] != 0).sum()),
                    peak_bytes=int((w.device.memory_stats() or {}).get(
                        "peak_bytes_in_use", 0)))
                for chunks in args.chunks:
                    cr.stream_chunks = chunks
                    step(0)
                    step(1)  # a chunked launch's rung compiles here
                    walls = [step((i + 2) % 4) for i in range(args.computes)]
                    say(what="stream_chunks", chunks=chunks, walls_s=walls)
        finally:
            cr.dispose()
    if len(forces) == 2:
        a, b = (forces[k].view(np.uint32) for k in ("vector", "components"))
        say(what="vector against components", differing_words=int((a != b).sum()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
