"""Flash-attention tile sweep CLI: brute-force every LEGAL
(block_q, block_k) pair at the requested geometry, then pin the block
autotuner's pick against the sweep optimum (``choice_vs_optimum`` —
ISSUE 16).

Run on the target chip from the repo root:

    python tools/block_sweep.py [--shape 1x1024x8x128] [--causal]
                                [--reps 3] [--precision default]
                                [--store DIR] [--json]

Per pair: the measured wall (best of ``--reps``), the sweep optimum,
the static ``default_blocks`` fallback pair, and what a fresh
:class:`BlockTuner` fed EXACTLY the sweep's walls engages.
``choice_vs_optimum`` == 1.0 means the tuner lands on the measured
best tile; hysteresis keeping a within-8% incumbent is the only
designed way it can exceed 1.0 + noise.  ``--store DIR`` persists each
pair's row to a kernel-profile store and then proves the warm start: a
SECOND store-seeded tuner must adopt the optimum without measuring.
On CPU rigs the kernels run in Pallas interpret mode — walls are
mock-meaningful, the pinning logic is identical.
"""

import argparse
import json
import os
import sys
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


def sweep(shape, causal: bool, reps: int, precision: str,
          store_dir=None) -> dict:
    """The artifact: every legal pair timed, tuner pick pinned."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cekirdekler_tpu.core.blocktuner import BlockTuner, legal_block_grid
    from cekirdekler_tpu.ops.flash_attention import (
        default_blocks, flash_attention)

    b, t, h, d = shape
    grid = legal_block_grid(t, t)
    sig = ("flash_attention.highest" if precision == "highest"
           else "flash_attention.bf16_default")
    out = {
        "shape": list(shape), "causal": causal, "precision": precision,
        "kernel_sig": sig, "grid": [list(p) for p in grid],
        "fallback": None, "rows": [],
    }
    fb = default_blocks(t, t)
    out["fallback"] = None if fb is None else list(fb)
    if not grid:
        out["note"] = (f"T={t}: no legal tile (no >=128 power-of-two "
                       "divisor) — the default path runs dense here")
        return out

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, t, h, d), dtype=np.float32))
    k = jnp.asarray(rng.standard_normal((b, t, h, d), dtype=np.float32))
    v = jnp.asarray(rng.standard_normal((b, t, h, d), dtype=np.float32))

    def time_pair(bq: int, bk: int) -> float:
        f = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal, bq, bk, None, precision))
        f(q, k, v).block_until_ready()  # compile outside the clock
        best = float("inf")
        for _ in range(max(1, reps)):
            t0 = time.perf_counter()
            f(q, k, v).block_until_ready()
            best = min(best, (time.perf_counter() - t0) * 1e3)
        return best

    # the brute force: every legal pair, timed
    tuner = BlockTuner()  # fresh — fed ONLY this sweep's walls
    walls = {}
    for bq, bk in grid:
        w = time_pair(bq, bk)
        walls[(bq, bk)] = w
        tuner.observe(sig, t, t, (bq, bk), w)
        out["rows"].append({"block_q": bq, "block_k": bk,
                            "wall_ms": round(w, 4)})
    best_pair = min(walls, key=lambda p: (walls[p], p[0] * p[1], p[0]))
    choice = tuner.choose(sig, t, t, shape=shape)
    out["sweep_best"] = list(best_pair)
    out["sweep_best_ms"] = round(walls[best_pair], 4)
    out["tuner_choice"] = None if choice is None else list(choice)
    out["tuner_choice_ms"] = (None if choice is None
                              else round(walls[choice], 4))
    out["choice_vs_optimum"] = (
        None if choice is None or walls[best_pair] <= 0.0
        else round(walls[choice] / walls[best_pair], 4))

    if store_dir:
        from cekirdekler_tpu.trace.device import ProfileStore

        store = ProfileStore(store_dir)
        for (bq, bk), w in walls.items():
            store.put(sig, shape, (bq, bk), {"device_ms": round(w, 4)})
        # the warm-start proof: a SECOND tuner, store-seeded, must
        # adopt the sweep optimum on first contact without measuring
        warm = BlockTuner(store=store)
        wchoice, wwhy = warm._choose_full(sig, t, t, shape=shape)
        out["warm_start"] = {
            "choice": None if wchoice is None else list(wchoice),
            "why": wwhy,
            "agrees_with_optimum": wchoice == best_pair,
        }
    return out


def _parse_shape(s: str):
    parts = tuple(int(v) for v in s.lower().split("x"))
    if len(parts) != 4 or any(p <= 0 for p in parts):
        raise argparse.ArgumentTypeError(
            f"--shape wants BxTxHxD (e.g. 1x1024x8x128), got {s!r}")
    return parts


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shape", type=_parse_shape, default=(1, 1024, 8, 128),
                    help="BxTxHxD geometry (default 1x1024x8x128)")
    ap.add_argument("--causal", action="store_true",
                    help="sweep the causal-masked kernel")
    ap.add_argument("--reps", type=int, default=3,
                    help="timed runs per pair (best kept)")
    ap.add_argument("--precision", default="default",
                    choices=("default", "highest"),
                    help="matmul precision (selects the kernel signature)")
    ap.add_argument("--store", metavar="DIR", default=None,
                    help="also persist rows to a kernel-profile store "
                         "and prove the warm start from it")
    ap.add_argument("--json", action="store_true",
                    help="print the raw JSON artifact only")
    args = ap.parse_args()

    out = sweep(args.shape, args.causal, args.reps, args.precision,
                store_dir=args.store)
    if args.json:
        print(json.dumps(_json_safe(out), allow_nan=False))
        return
    b, t, h, d = out["shape"]
    print(f"flash {out['kernel_sig']} B={b} T={t} H={h} D={d} "
          f"causal={out['causal']}")
    if not out["rows"]:
        print(out.get("note", "no legal tiles"))
        return
    print(f"{'block_q':>8} {'block_k':>8} {'wall ms':>10}")
    for r in out["rows"]:
        mark = ""
        if [r["block_q"], r["block_k"]] == out["sweep_best"]:
            mark += " <- sweep optimum"
        if out["fallback"] and [r["block_q"], r["block_k"]] == out["fallback"]:
            mark += " (static default_blocks)"
        print(f"{r['block_q']:>8} {r['block_k']:>8} "
              f"{r['wall_ms']:>10.4f}{mark}")
    print(f"tuner chose {out['tuner_choice']} "
          f"({out['tuner_choice_ms']} ms) vs optimum {out['sweep_best']} "
          f"({out['sweep_best_ms']} ms): choice_vs_optimum = "
          f"{out['choice_vs_optimum']}")
    if "warm_start" in out:
        ws = out["warm_start"]
        print(f"store warm start: choice {ws['choice']} why={ws['why']} "
              f"agrees_with_optimum={ws['agrees_with_optimum']}")


if __name__ == "__main__":
    main()
