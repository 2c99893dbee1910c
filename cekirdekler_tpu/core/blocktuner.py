"""Measured Pallas tile/block autotuner — the ProfileStore's first
consumer (ISSUE 16).

``ops/flash_attention.py``'s default-argument block policy was a static
gcd heuristic (``default_blocks``): one 512 target measured once on one
chip, degraded by divisibility.  The kernel-profile store
(``trace/device.ProfileStore``) and the roofline classifier
(``roofline_row``) have been persisting exactly the evidence a measured
policy needs since PR 8 — per (kernel signature, shape, blocks) device
walls and compute- vs memory-bound verdicts — with zero consumers.
This module cashes that in, reusing the proven ``TransferTuner`` idiom
(``core/stream.py``):

- **first contact** per (kernel signature, (Tq, Tk), device kind) seeds
  from the ProfileStore when rows exist (warm start — no measuring run),
  else falls back to the static ``default_blocks`` pair until a
  deliberate :meth:`BlockTuner.measuring_run` walks a small candidate
  grid of LEGAL tile shapes (each block divides its sequence length and
  is >= the dense floor), oriented by the roofline bound when known —
  compute-bound kernels probe big MXU-resident tiles first,
  memory-bound kernels probe small working sets first;
- **EMA refinement**: every observed wall EMAs into the candidate's
  estimate, so link/chip weather tracks without one spike owning it;
- **hysteresis**: an engaged choice changes only when a challenger's
  measured wall beats the incumbent's by more than
  :data:`HYSTERESIS_FRAC` — a ±noise re-measure cannot flap the choice
  (and thereby thrash the executable cache: a kept geometry is a kept
  compiled ladder);
- **provenance**: the whole choice arithmetic lives in ONE pure,
  ckmodel-purity-lint-clean transition function
  (:func:`block_transition`), and every transition that CHANGES the
  engaged choice records a replayable ``block-retune`` decision —
  ``ckreplay verify`` re-executes it bit-identically, ``ckreplay whatif
  --set block_grid=...`` counterfactuals the candidate grid, and the
  bounded model checker (``analysis/model.BlockMachine``) explores it
  against the declared :data:`MODEL_INVARIANTS`.

The stateful wrapper (:class:`BlockTuner`) follows the TransferTuner
lock discipline exactly: one mutex, VALUE copies of shared state read
under it, decision/flight records emitted OUTSIDE it, metric handles
cached at construction (the ckcheck hot-path contract —
``BlockTuner.choose`` is a declared hot root)."""

from __future__ import annotations

import threading
from dataclasses import dataclass

from ..metrics.registry import REGISTRY
from ..obs.decisions import DECISIONS

__all__ = [
    "BLOCK_CANDIDATES",
    "DENSE_FLOOR",
    "HYSTERESIS_FRAC",
    "MODEL_INVARIANTS",
    "legal_block_grid",
    "orient_block_grid",
    "clamp_blocks",
    "block_transition",
    "BlockTuner",
    "TUNER",
]

#: Candidate per-axis tile sizes: powers of two spanning the useful range
#: (128² tiles leave the MXU mostly idle; larger blocks amortize the
#: per-block softmax work).  The top is what Mosaic ACCEPTS: compiled on a
#: v5e (16 MiB scoped VMEM, D=64 — which pads to the same 128-lane tiles
#: as D=128), every fwd and bwd kernel of every pair up to 1024/1024
#: builds in both precision modes, while most pairs with a 2048 side are
#: refused (f32 streams: 512/2048, 1024/2048, 2048/256 and up; bf16
#: streams: 1024/2048, 2048/1024, 2048/2048 — PR 21 chip run).  A
#: candidate the chip refuses is not in the grid; it is never offered
#: and then caught at run time.
BLOCK_CANDIDATES = (128, 256, 512, 1024)

#: Smallest legal block per axis — mirrors ``ops.flash_attention``'s
#: ``_DENSE_FLOOR``: below one full 128-lane MXU tile the per-block
#: softmax VPU work dominates and dense XLA attention wins.
DENSE_FLOOR = 128

#: A challenger must beat the incumbent's EMA wall by MORE than this
#: fraction to displace it.  8% sits above the per-candidate wall noise
#: observed in the r5 block sweep (~3-5% run-to-run on a quiet chip)
#: and below the ~15-50% gaps between adjacent grid points — noise
#: cannot flap the choice, real cliffs still switch it.
HYSTERESIS_FRAC = 0.08

#: EMA weight for observed walls (the TransferTuner constant).
EMA_ALPHA = 0.5

#: A deliberate measuring run probes at most this many oriented grid
#: candidates — "a small candidate grid", not an exhaustive sweep
#: (tools/block_sweep.py is the exhaustive honesty check).
MEASURE_GRID_CAP = 6

#: The properties the bounded model checker
#: (``analysis/model.BlockMachine``) explores :func:`block_transition`
#: against — each with a deliberately-broken fixture in
#: tests/test_ckmodel.py proving the checker would catch its loss.
MODEL_INVARIANTS = (
    ("choice-legality", "safety",
     "every engaged choice is a legal tile pair — each block divides "
     "its sequence length, is >= the dense floor, and sits in the "
     "candidate grid; cold/no-grid transitions return None with a "
     "named why, never an illegal pair"),
    ("hysteresis-bound", "safety",
     "an engaged choice changes only when the challenger's measured "
     "wall beats the incumbent's by more than the hysteresis fraction "
     "— a ±noise re-measure can never flap the choice (and thrash the "
     "executable cache behind it)"),
    ("retune-visibility", "safety",
     "every transition that changes the engaged choice emits a "
     "block-retune decision row whose outputs equal the transition's "
     "returned choice — no silent retunes"),
)


# -- the pure surface (declared in tools/ckmodel/purity.py) ----------------


def legal_block_grid(tq, tk, floor=DENSE_FLOOR,
                     candidates=BLOCK_CANDIDATES):
    """The legal (block_q, block_k) candidate grid for sequence lengths
    (tq, tk): per axis, every candidate that divides the length and is
    >= the floor.  Empty exactly when :func:`default_blocks` would fall
    back to dense attention (both are gated on a >= 128 power-of-two
    divisor), so the tuner and the static policy agree on WHEN tiling
    is legal and only ever disagree on WHICH legal tile to run."""
    qs = tuple(c for c in candidates if floor <= c <= tq and tq % c == 0)
    ks = tuple(c for c in candidates if floor <= c <= tk and tk % c == 0)
    return tuple((bq, bk) for bq in qs for bk in ks)


def orient_block_grid(grid, bound):
    """Measuring-run probe order for a legal grid, oriented by the
    roofline classification (``trace/device.roofline_row``'s ``bound``
    field) when the caller knows it: a compute-bound kernel probes
    LARGE tiles first (MXU residency per launch is the lever), a
    memory-bound kernel probes SMALL tiles first (the VMEM working set
    is), unknown keeps the grid's natural ascending order.  Orientation
    only reorders — under :data:`MEASURE_GRID_CAP` it decides which
    candidates a capped measuring run actually pays for."""
    if bound == "compute":
        return tuple(sorted(grid, key=lambda p: (-p[0] * p[1], -p[0])))
    if bound == "memory":
        return tuple(sorted(grid, key=lambda p: (p[0] * p[1], p[0])))
    return tuple(grid)


def clamp_blocks(blocks, grid):
    """Snap a (possibly store-inherited, possibly from another rig)
    block pair onto the legal grid: exact membership wins, else the
    nearest legal pair by per-axis distance (deterministic ties: the
    smaller area, then the smaller block_q).  None when the grid is
    empty or the pair is unusable."""
    if not grid or blocks is None:
        return None
    pair = (int(blocks[0]), int(blocks[1]))
    if pair in grid:
        return pair
    return min(grid, key=lambda p: (abs(p[0] - pair[0]) + abs(p[1] - pair[1]),
                                    p[0] * p[1], p[0]))


def block_transition(current, walls, grid, hysteresis=HYSTERESIS_FRAC,
                     seed=None, fallback=None):
    """THE pure block-choice transition: one ``(choice, why)`` from one
    consistent snapshot — the stateful wrapper only snapshots inputs
    and applies outputs, so replay-verify and the bounded model checker
    exercise the REAL arithmetic.

    - ``current``: the engaged pair, or None before engagement;
    - ``walls``: iterable of ``(pair, ema_wall_ms)`` measurements
      (order-irrelevant — sorted internally);
    - ``grid``: the legal candidate pairs (:func:`legal_block_grid`);
    - ``seed``: a ProfileStore-inherited pair consulted only while no
      wall is measured (the warm start);
    - ``fallback``: the static ``default_blocks`` pair, the cold-start
      answer when neither measurement nor seed exists.

    why ∈ {no-legal-grid, store-seed, cold-fallback, cold,
    measuring, steady, hysteresis-hold, model}."""
    if not grid:
        return None, "no-legal-grid"
    gset = set(grid)
    known = sorted(
        (tuple(p), float(w)) for p, w in walls
        if tuple(p) in gset and w is not None and w >= 0.0
    )
    if not known:
        if seed is not None:
            snapped = clamp_blocks(seed, grid)
            if snapped is not None:
                return snapped, "store-seed"
        if fallback is not None and tuple(fallback) in gset:
            return tuple(fallback), "cold-fallback"
        return None, "cold"
    best, best_w = None, None
    for p, w in known:
        # argmin; ties (exact equality after the sort) keep the
        # smaller-area, smaller-bq pair — the sort order
        if best_w is None or w < best_w - 1e-12:
            best, best_w = p, w
    cur = None if current is None else tuple(current)
    cur_w = dict(known).get(cur) if cur is not None else None
    if cur is not None and cur_w is None:
        # the incumbent has no measured wall yet (store-seeded or
        # cold-fallback engagement): the first measurement set decides
        return (cur, "steady") if best == cur else (best, "measuring")
    if best == cur:
        return cur, "steady"
    if cur is not None and best_w >= cur_w * (1.0 - hysteresis):
        return cur, "hysteresis-hold"
    return best, "model"


# -- the stateful wrapper --------------------------------------------------


@dataclass
class _WallObs:
    """EMA of one candidate pair's observed wall."""

    wall_ms: float
    count: int = 1


class BlockTuner:
    """Online Pallas block-shape autotuner (see module docstring).
    Thread-safe: concurrent observers and choosers share one mutex;
    ``choose`` reads a consistent snapshot and records outside it."""

    def __init__(self, candidates=BLOCK_CANDIDATES,
                 hysteresis=HYSTERESIS_FRAC, ema=EMA_ALPHA,
                 floor=DENSE_FLOOR, store=None, device_kind=None):
        self.candidates = tuple(sorted(set(int(c) for c in candidates)))
        self.hysteresis = float(hysteresis)
        self.ema = float(ema)
        self.floor = int(floor)
        self._store = store  # None → trace.device.STORE, resolved lazily
        self._walls: dict[tuple, dict[tuple, _WallObs]] = {}
        self._choice: dict[tuple, tuple] = {}
        #: keys whose ProfileStore seed lookup already ran (hit or miss)
        #: — the store is file-backed; one read per key, ever
        self._seed_checked: set[tuple] = set()
        self._seed: dict[tuple, tuple] = {}
        self.retunes = 0
        self._device_kind = device_kind
        self._mu = threading.Lock()
        # metric handles cached at construction — the hot-path contract
        # (choose() sits on the flash default-argument path)
        self._m_choose = REGISTRY.counter(
            "ck_block_choose_total",
            "block-shape choices served by the tuner")
        self._m_retunes = REGISTRY.counter(
            "ck_block_retunes_total",
            "engaged block choices changed (incl. first engagement)")
        self._m_seeds = REGISTRY.counter(
            "ck_block_store_seeds_total",
            "warm starts adopted from the kernel-profile store")
        self._m_measure = REGISTRY.counter(
            "ck_block_measure_runs_total",
            "deliberate measuring runs over the candidate grid")

    # -- keys / environment --------------------------------------------------
    def device_kind(self) -> str:
        """The rig's device kind (``jax.Device.device_kind``), resolved
        once: the same kernel+shape on a v5e and a CPU container are two
        different wall stories and must never share a row."""
        if self._device_kind is None:
            try:
                import jax

                self._device_kind = str(jax.devices()[0].device_kind)
            except Exception:  # noqa: BLE001 - no backend is still a kind
                self._device_kind = "unknown"
        return self._device_kind

    def _key(self, kernel_sig, tq: int, tk: int) -> tuple:
        return (str(kernel_sig), (int(tq), int(tk)), self.device_kind())

    # -- ProfileStore seam ---------------------------------------------------
    def _store_seed(self, kernel_sig, shape) -> tuple | None:
        """Best stored blocks for (kernel_sig, shape) — the warm start.
        File IO: called OUTSIDE the mutex, once per key ever."""
        store = self._store
        if store is None:
            from ..trace.device import STORE as store  # noqa: N811
        try:
            return store.best_blocks(kernel_sig, shape)
        except Exception:  # noqa: BLE001 - a corrupt store row is a miss
            return None

    # -- inputs --------------------------------------------------------------
    def observe(self, kernel_sig, tq: int, tk: int, blocks,
                wall_ms: float) -> None:
        """EMA one measured wall for a candidate pair.  No decision is
        recorded here — the next :meth:`choose` snapshots the updated
        walls into its own replayable record."""
        key = self._key(kernel_sig, tq, tk)
        pair = (int(blocks[0]), int(blocks[1]))
        w = max(float(wall_ms), 0.0)
        with self._mu:
            rows = self._walls.setdefault(key, {})
            cur = rows.get(pair)
            if cur is None:
                rows[pair] = _WallObs(w)
            else:
                cur.wall_ms += self.ema * (w - cur.wall_ms)
                cur.count += 1

    # -- the choice ----------------------------------------------------------
    def choose(self, kernel_sig, tq: int, tk: int, shape=None,
               fallback=None):
        """The engaged (block_q, block_k) for this key, or None when no
        legal tile exists (caller falls back to dense).  First contact
        consults the ProfileStore (warm start), then the static
        ``fallback`` pair; measured walls take over as they arrive.
        Every choice CHANGE records one replayable ``block-retune``
        decision and a ``block-retune`` flight event."""
        pair, _why = self._choose_full(kernel_sig, tq, tk, shape=shape,
                                       fallback=fallback)
        return pair

    def prewarm(self, kernel_sig, tq: int, tk: int, shape=None,
                fallback=None):
        """AOT-warmup seam (core/compilecache.py, tools/coldstart.py):
        engage this shape's choice BEFORE its first live call, so the
        executable the warmup path compiles — and the persistent cache
        stores — is the TUNED block geometry, not the static fallback a
        cold tuner would hand the first caller.  The ProfileStore is
        file-backed, so a warm-from-disk process re-engages the SAME
        pair the populating process measured (same blocks → same Pallas
        executable → XLA persistent-cache hit).  Returns the engaged
        pair (None: caller warms the dense path)."""
        return self.choose(kernel_sig, tq, tk, shape=shape,
                           fallback=fallback)

    def _choose_full(self, kernel_sig, tq: int, tk: int, shape=None,
                     fallback=None):
        tq, tk = int(tq), int(tk)
        key = self._key(kernel_sig, tq, tk)
        grid = legal_block_grid(tq, tk, self.floor, self.candidates)
        with self._mu:
            need_seed = (bool(grid) and key not in self._seed_checked
                         and not self._walls.get(key)
                         and key not in self._choice)
        if need_seed:
            # store lookup outside the mutex (file IO); idempotent if
            # two first-contact threads race it
            seed = self._store_seed(kernel_sig,
                                    shape if shape is not None else (tq, tk))
            with self._mu:
                self._seed_checked.add(key)
                if seed is not None:
                    self._seed[key] = (int(seed[0]), int(seed[1]))
        with self._mu:
            # VALUE copies under the mutex — concurrent observe() EMAs
            # the _WallObs rows in place; modeling (and recording) torn
            # state would make the recorded snapshot disagree with the
            # choice replay-verify re-derives from it
            walls = tuple(sorted(
                (p, o.wall_ms) for p, o in self._walls.get(key, {}).items()
            ))
            current = self._choice.get(key)
            seed = self._seed.get(key)
        fb = None if fallback is None else (int(fallback[0]),
                                            int(fallback[1]))
        choice, why = block_transition(
            current, walls, grid, hysteresis=self.hysteresis,
            seed=seed, fallback=fb,
        )
        changed = choice is not None and choice != current
        rec = None
        if changed and DECISIONS.enabled:
            rec = {
                "kernel_sig": str(kernel_sig),
                "shape": list(shape) if shape is not None else [tq, tk],
                "tq": tq, "tk": tk,
                "device_kind": key[2],
                "grid": [list(p) for p in grid],
                "walls": [[list(p), w] for p, w in walls],
                "current": None if current is None else list(current),
                "seed": None if seed is None else list(seed),
                "fallback": None if fb is None else list(fb),
                "hysteresis": self.hysteresis,
            }
        if changed:
            with self._mu:
                self._choice[key] = choice
                self.retunes += 1
        self._m_choose.inc()
        if changed:
            self._m_retunes.inc()
            if why == "store-seed":
                self._m_seeds.inc()
            # decision + flight OUTSIDE the mutex (recorder discipline)
            if rec is not None and DECISIONS.enabled:
                DECISIONS.record("block-retune", rec, {
                    "block_q": choice[0], "block_k": choice[1], "why": why,
                })
            from ..obs.flight import FLIGHT

            if FLIGHT.enabled:
                FLIGHT.event(
                    "block-retune", kernel=str(kernel_sig), tq=tq, tk=tk,
                    block_q=choice[0], block_k=choice[1], why=why,
                )
        return choice, why

    # -- the deliberate measuring run ----------------------------------------
    def measuring_run(self, kernel_sig, tq: int, tk: int, runner,
                      shape=None, bound=None, reps: int = 1,
                      limit: int = MEASURE_GRID_CAP) -> dict:
        """Walk a small oriented candidate grid, timing ``runner(bq,
        bk) -> wall_ms`` per candidate (best of ``reps``), feed every
        wall through :meth:`observe`, then engage via :meth:`choose`.
        A ProfileStore-seeded key SKIPS the walk — the warm start is
        the whole point of persisting profiles.  ``bound`` orients the
        walk (:func:`orient_block_grid`) and bounds what a capped run
        pays for."""
        tq, tk = int(tq), int(tk)
        grid = legal_block_grid(tq, tk, self.floor, self.candidates)
        if not grid:
            return {"measured": [], "chosen": None, "why": "no-legal-grid",
                    "skipped": None}
        choice, why = self._choose_full(kernel_sig, tq, tk, shape=shape)
        if why == "store-seed":
            return {"measured": [], "chosen": choice, "why": why,
                    "skipped": "store-seed"}
        self._m_measure.inc()
        measured = []
        for bq, bk in orient_block_grid(grid, bound)[:max(1, int(limit))]:
            wall = min(float(runner(bq, bk)) for _ in range(max(1, reps)))
            self.observe(kernel_sig, tq, tk, (bq, bk), wall)
            measured.append({"block_q": bq, "block_k": bk,
                             "wall_ms": wall})
        choice, why = self._choose_full(kernel_sig, tq, tk, shape=shape)
        return {"measured": measured, "chosen": choice, "why": why,
                "skipped": None}

    # -- lifecycle -----------------------------------------------------------
    def on_invalidate(self, kernel_sig=None) -> None:
        """Geometry/rig change: measured walls describe kernels that no
        longer run — drop them (one signature, or everything) so the
        next contact re-seeds and re-measures."""
        with self._mu:
            if kernel_sig is None:
                dropped = len(self._choice) + len(self._walls)
                self._walls.clear()
                self._choice.clear()
                self._seed.clear()
                self._seed_checked.clear()
            else:
                sig = str(kernel_sig)
                doomed = [k for k in set(self._walls) | set(self._choice)
                          if k[0] == sig]
                dropped = len(doomed)
                for k in doomed:
                    self._walls.pop(k, None)
                    self._choice.pop(k, None)
                    self._seed.pop(k, None)
                    self._seed_checked.discard(k)
        from ..obs.flight import FLIGHT

        FLIGHT.event("block-retune", kernel=kernel_sig, why="invalidate",
                     dropped_keys=dropped)

    def snapshot(self) -> dict:
        """Value-copy view for tools/tests: key → {choice, walls,
        seed}."""
        with self._mu:
            keys = set(self._walls) | set(self._choice)
            return {
                k: {
                    "choice": self._choice.get(k),
                    "walls": {p: o.wall_ms
                              for p, o in self._walls.get(k, {}).items()},
                    "seed": self._seed.get(k),
                }
                for k in keys
            }


#: The process-wide tuner the flash default-argument path consults.
TUNER = BlockTuner()
