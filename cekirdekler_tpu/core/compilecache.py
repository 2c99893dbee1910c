"""Persistent executable cache + AOT warmup (ROADMAP item 4).

The in-process executable cache (``kernel/registry.KernelProgram._cache``)
honors the compile-once contract — a rebalance or a window-size change
never recompiles — but it dies with the process.  Every new serving-fabric
shard and every elastic rejoin re-paid the ladder compiles (measured at
~19x a timed wall when one lands inside a window).  This module is the
cross-process half:

- **XLA executable bytes** ride JAX's own persistent compilation cache,
  which this module never places: ``JAX_COMPILATION_CACHE_DIR`` decides
  where it lives, and when that is unset the package points it once, at
  import, at the fixed ``<checkout>/.jax_cache``.  The same place
  (``cekirdekler_tpu/__init__.py``) makes every executable persist (the
  ladder's rungs compile in well under jax's 1 s default floor).  A
  process that re-traces a ladder executable LOADS its XLA binary from
  there instead of recompiling.  :func:`trim_placed_jax_cache` bounds
  the directory the package placed.
- **Ladder-level manifest**: XLA's cache can only answer "have I compiled
  this exact computation" — it cannot tell a joining shard *what to
  trace*.  ``<dir>/entries/<key>.json`` persists one :class:`WarmupSpec`
  per distinct ladder key (kernel signature + ladder geometry via
  ``core/stream.plan_signature`` + operand shapes + baked values + device
  kind + jax version), so :func:`warm_from_disk` can re-trace a fleet's
  whole signature mix in a cold process and have every XLA compile served
  from disk.  ``<dir>/manifest.jsonl`` is the append-only index
  (write/hit/miss/evict rows; one ``O_APPEND`` line per row).

Durability discipline (the utils/checkpoint idiom): entry payloads are
written tmp+rename (a killed writer never leaves a half entry; two
processes racing one key both rename identical content — last one wins,
harmlessly), manifest rows are single-line appends, and EVERY read path
tolerates torn/corrupt state: a truncated manifest row or an unparsable
payload is a *named miss* (``miss_reasons``), never an exception.  An
unset ``CK_COMPILE_CACHE`` disables the manifest layer entirely — warmup
still precompiles in-process, results are bit-identical either way.

``CK_COMPILE_CACHE_MAX_MB`` (default 512) caps each layer separately:
the package-placed XLA directory is trimmed to it whenever a ``Cores`` is
built (:func:`trim_placed_jax_cache`), and
:meth:`CompileCache.prune` holds ``entries/`` under it — oldest-mtime
files first (hits refresh an entry's mtime), one ``evict`` row per
removal.  ``tools/ckcache.py`` is the operator CLI (``ls`` / ``stats`` /
``prune`` / ``--verify``) over the manifest layer.

Cache I/O happens only on COLD paths — warmup, window engagement, the
CLI — never on the fused-defer hot path (the ckcheck contract); metric
handles are cached at module import.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
import time
from dataclasses import dataclass

from ..metrics.registry import REGISTRY
from ..obs.decisions import DECISIONS
from ..obs.flight import FLIGHT
from .stream import plan_signature
from .worker import launch_ladder

__all__ = [
    "CACHE_ENV",
    "CACHE_MAX_MB_ENV",
    "WarmupSpec",
    "CompileCache",
    "CACHE",
    "warm_from_disk",
    "probe_counts",
    "trim_placed_jax_cache",
]

CACHE_ENV = "CK_COMPILE_CACHE"
CACHE_MAX_MB_ENV = "CK_COMPILE_CACHE_MAX_MB"

#: Default LRU byte cap over ``entries/``.
DEFAULT_MAX_MB = 512

#: Manifest format tag (first line of every manifest).
SCHEMA = "ck-compile-cache-v1"

# cached handles — lookups/records run per warmed key (cold), but the
# registry get-or-create discipline is uniform package-wide (PR 4)
_M_HIT = REGISTRY.counter(
    "ck_compile_cache_hit_total",
    "persistent-cache lookups that found a manifest entry")
_M_MISS = REGISTRY.counter(
    "ck_compile_cache_miss_total",
    "persistent-cache lookups that missed (incl. named corrupt-entry misses)")
_M_WRITE = REGISTRY.counter(
    "ck_compile_cache_write_total",
    "ladder-spec entries written to the persistent cache")
_M_EVICT = REGISTRY.counter(
    "ck_compile_cache_evict_total",
    "files evicted by the persistent cache's LRU size cap")


def trim_placed_jax_cache(max_bytes: int | None = None) -> int:
    """Hold the jax cache directory THE PACKAGE PLACED
    (``cekirdekler_tpu.PLACED_CACHE_DIR``) under the cap: delete
    oldest-written executables until it fits, return how many went.
    Called wherever a ``Cores`` is built (process start, a fabric join):
    between calls the directory grows by what the process compiles, which
    its in-memory executable cache holds as well.  A directory placed from
    outside belongs to whoever placed it — nothing in it is deleted.

    Not jax's own eviction (``jax_compilation_cache_max_size``): that
    re-reads one stamp file per entry on EVERY write, under a file lock —
    with a few hundred ladder executables on disk it doubled the cold
    first-call cost on the chip (PERF.md, PR 21)."""
    from .. import PLACED_CACHE_DIR

    if PLACED_CACHE_DIR is None:
        return 0
    cap = CACHE.max_bytes() if max_bytes is None else int(max_bytes)
    try:
        with os.scandir(PLACED_CACHE_DIR) as entries:
            files = sorted(
                (e.stat().st_mtime, e.stat().st_size, e.path)
                for e in entries if e.name.endswith("-cache"))
    except OSError:  # not there yet: nothing to trim
        return 0
    total = sum(size for _t, size, _p in files)
    evicted = 0
    for _t, size, path in files:
        if total <= cap:
            break
        try:
            os.remove(path)
            evicted += 1
        except OSError:  # another process trimmed it first
            pass
        total -= size
    return evicted


def probe_counts() -> tuple[int, int]:
    """Current (hit, miss) probe totals — the fused-batch phase hook's
    sampling point (``Cores.compute_fused_batch`` reads a before/after
    delta so the serving tier can stamp a ``warm-compile``
    request-lifecycle phase when a window paid a compile miss)."""
    return (int(_M_HIT.value), int(_M_MISS.value))


def _canon_values(value_args) -> list:
    """JSON-stable form of a launch's value arguments (dict → sorted
    ``[name, [vals...]]`` pairs; sequence → one list)."""
    if isinstance(value_args, dict):
        return [[str(k), [_scalar(v) for v in vals]]
                for k, vals in sorted(value_args.items())]
    return [_scalar(v) for v in value_args]


def _scalar(v):
    """Native-python scalar (np.float32 etc. are not JSON; their repr
    drift would also split keys across processes)."""
    try:
        if isinstance(v, bool):
            return v
        if isinstance(v, (int, float, str)):
            return v
        return float(v)
    except Exception:  # noqa: BLE001 - unhashable/exotic: keyed by repr
        return repr(v)


def _freeze(values) -> tuple:
    """Deep-tuple a canonical value list: :attr:`WarmupSpec.values` must
    be hashable all the way down (it sits in dedup sets and dataclass
    hashes), and JSON hands back nested LISTS."""
    return tuple(_freeze(v) if isinstance(v, (list, tuple)) else v
                 for v in values)


def _restore_values(values):
    """Inverse of :func:`_canon_values` for the dict form (list-of-pairs
    round-trips back to ``{name: tuple}``; flat lists stay tuples)."""
    if values and all(
        isinstance(p, (list, tuple)) and len(p) == 2
        and isinstance(p[0], str) and isinstance(p[1], (list, tuple))
        for p in values
    ):
        return {k: tuple(v) for k, v in values}
    return tuple(values)


@dataclass(frozen=True)
class WarmupSpec:
    """One warmable launch shape: everything the AOT path needs to
    re-trace a workload's full predicated launch ladder WITHOUT the
    workload's live arrays — operand sizes/dtypes, not identities
    (identity is the coalescing key; shape is the compile key).

    ``values`` holds the canonical (:func:`_canon_values`) form so a
    spec that round-tripped through JSON builds the identical
    ``fused_launcher`` key as one built from a live job."""

    kernels: tuple
    params: tuple            # ((size, dtype_str), ...)
    global_range: int
    local_range: int
    global_offset: int = 0
    compute_id: int = 0
    values: tuple = ()

    @staticmethod
    def from_job(kernel_names, params, compute_id, global_range,
                 local_range, global_offset=0, value_args=()) -> "WarmupSpec":
        """Capture a live call's shape — reads ``size``/``dtype`` off the
        params, never their data."""
        shapes = tuple(
            (int(p.size), str(getattr(p, "dtype", "float32")))
            for p in params
        )
        return WarmupSpec(
            kernels=tuple(str(k) for k in kernel_names), params=shapes,
            global_range=int(global_range), local_range=int(local_range),
            global_offset=int(global_offset), compute_id=int(compute_id),
            values=_freeze(json.loads(
                json.dumps(_canon_values(value_args), allow_nan=False))),
        )

    def value_args(self):
        """The live-key form of :attr:`values` (dict or tuple)."""
        return _restore_values(self.values)

    def ladder(self) -> list[int]:
        """This spec's binary launch ladder (the worker's own
        decomposition — one source of truth for the geometry)."""
        return launch_ladder(self.global_range, self.local_range)

    def to_payload(self) -> dict:
        return {
            "kernels": list(self.kernels),
            "params": [[s, d] for s, d in self.params],
            "global_range": self.global_range,
            "local_range": self.local_range,
            "global_offset": self.global_offset,
            "compute_id": self.compute_id,
            "values": _canon_values(self.value_args()),
        }

    @staticmethod
    def from_payload(doc: dict) -> "WarmupSpec":
        return WarmupSpec(
            kernels=tuple(str(k) for k in doc["kernels"]),
            params=tuple((int(s), str(d)) for s, d in doc["params"]),
            global_range=int(doc["global_range"]),
            local_range=int(doc["local_range"]),
            global_offset=int(doc.get("global_offset", 0)),
            compute_id=int(doc.get("compute_id", 0)),
            values=_freeze(json.loads(
                json.dumps(doc.get("values", []), allow_nan=False))),
        )


def program_fingerprint(program) -> str:
    """Kernel-signature component of the cache key: the C source text
    plus the python-kernel names — two programs with equal names but
    different bodies must never share executables."""
    h = hashlib.sha256()
    h.update(getattr(program, "source", "").encode())
    for name in sorted(getattr(program, "_py_kernels", {}) or ()):
        h.update(b"|py:" + name.encode())
    return h.hexdigest()[:16]


class CompileCache:
    """The on-disk, cross-process executable cache (module docstring).

    ``root=None`` (the singleton) re-reads ``CK_COMPILE_CACHE`` per
    operation, so arming/disarming via the environment needs no object
    rebuild; an explicit root pins it (tests, the CLI)."""

    def __init__(self, root: str | None = None):
        self._root = root
        self._armed_dir: str | None = None
        #: keys already looked up or recorded this process — the
        #: engage-time recorder pays at most one disk probe per key
        self._seen: set[str] = set()
        #: named reasons for degraded reads (torn row, bad payload...)
        self.miss_reasons: dict[str, int] = {}

    # -- environment ---------------------------------------------------------
    @property
    def root(self) -> str | None:
        if self._root is not None:
            return self._root
        r = os.environ.get(CACHE_ENV, "").strip()
        return r or None

    @property
    def enabled(self) -> bool:
        return self.root is not None

    def max_bytes(self) -> int:
        try:
            mb = float(os.environ.get(CACHE_MAX_MB_ENV, DEFAULT_MAX_MB))
        except ValueError:
            mb = DEFAULT_MAX_MB
        return int(mb * (1 << 20))

    def _entries_dir(self) -> str:
        return os.path.join(self.root, "entries")

    def _manifest(self) -> str:
        return os.path.join(self.root, "manifest.jsonl")

    # -- arming --------------------------------------------------------------
    def arm(self) -> bool:
        """Create the manifest layout under the root.  Idempotent.  Where
        the XLA bytes land, which of them persist and how many are kept
        is not decided here (module docstring)."""
        root = self.root
        if root is None:
            return False
        if self._armed_dir == root:
            return True
        os.makedirs(self._entries_dir(), exist_ok=True)
        self._armed_dir = root
        return True

    # -- keys ----------------------------------------------------------------
    def ladder_key(self, program, spec: WarmupSpec, platform: str | None,
                   donate: bool, device_kind: str) -> str:
        """The cross-process cache key: sha256 over the canonical JSON of
        every input the fused-ladder executable depends on — kernel
        signature, ladder geometry (``plan_signature`` over the worker's
        own decomposition), operand shapes, baked values, launch
        geometry, platform/donation, device kind, jax + backend
        version.  ``compute_id``/``global_offset`` are deliberately
        absent: both are runtime scalars of the cached executable."""
        try:
            import jax

            jax_ver = jax.__version__
        except Exception:  # noqa: BLE001 - keyed conservatively without jax
            jax_ver = "nojax"
        doc = {
            "program": program_fingerprint(program),
            "kernels": list(spec.kernels),
            "blocks": plan_signature(spec.ladder()),
            "params": [[s, d] for s, d in spec.params],
            "global_range": spec.global_range,
            "local_range": spec.local_range,
            "values": _canon_values(spec.value_args()),
            "platform": platform or "",
            "donate": bool(donate),
            "device_kind": device_kind,
            "jax": jax_ver,
        }
        blob = json.dumps(doc, sort_keys=True, separators=(",", ":"),
                          allow_nan=False)
        return hashlib.sha256(blob.encode()).hexdigest()[:32]

    # -- degraded-read bookkeeping -------------------------------------------
    def _named_miss(self, reason: str) -> None:
        self.miss_reasons[reason] = self.miss_reasons.get(reason, 0) + 1
        _M_MISS.inc()

    # -- reads ---------------------------------------------------------------
    def lookup(self, key: str, count: bool = True) -> bool:
        """True iff a WELL-FORMED entry for ``key`` exists.  A missing,
        torn, or unparsable entry is a (named) miss — never an
        exception.  A hit refreshes the entry's mtime (the LRU clock)
        and appends a ``hit`` manifest row."""
        if not self.enabled:
            return False
        path = os.path.join(self._entries_dir(), key + ".json")
        try:
            with open(path, "rb") as f:
                doc = json.loads(f.read().decode())
            WarmupSpec.from_payload(doc["spec"])
        except FileNotFoundError:
            if count:
                self._named_miss("absent")
                self._append_row({"op": "miss", "key": key,
                                  "reason": "absent"})
            return False
        except Exception:  # noqa: BLE001 - torn/corrupt payload = miss
            if count:
                self._named_miss("corrupt-entry")
                self._append_row({"op": "miss", "key": key,
                                  "reason": "corrupt-entry"})
            return False
        if count:
            _M_HIT.inc()
            try:
                os.utime(path, None)
            except OSError:
                pass
            self._append_row({"op": "hit", "key": key})
        self._seen.add(key)
        return True

    def load_specs(self) -> list[tuple[str, WarmupSpec]]:
        """Every well-formed ``(key, spec)`` on disk — the fleet's
        persisted signature mix.  Corrupt entries are skipped with a
        named miss (the torn-entry contract)."""
        if not self.enabled:
            return []
        out: list[tuple[str, WarmupSpec]] = []
        edir = self._entries_dir()
        try:
            names = sorted(os.listdir(edir))
        except OSError:
            return []
        for name in names:
            if not name.endswith(".json"):
                continue
            try:
                with open(os.path.join(edir, name), "rb") as f:
                    doc = json.loads(f.read().decode())
                out.append((doc["key"], WarmupSpec.from_payload(doc["spec"])))
            except Exception:  # noqa: BLE001 - corrupt entry = named miss
                self._named_miss("corrupt-entry")
        return out

    def manifest_rows(self) -> list[dict]:
        """Parsed manifest rows, torn lines skipped (named).  A manifest
        is append-only jsonl; a crashed writer's partial last line is
        expected state, not an error."""
        rows: list[dict] = []
        if not self.enabled:
            return rows
        try:
            with open(self._manifest(), "rb") as f:
                data = f.read().decode(errors="replace")
        except OSError:
            return rows
        for line in data.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                doc = json.loads(line)
                if isinstance(doc, dict):
                    rows.append(doc)
                else:
                    self._named_miss("torn-manifest-row")
            except Exception:  # noqa: BLE001 - torn row = named skip
                self._named_miss("torn-manifest-row")
        return rows

    # -- writes --------------------------------------------------------------
    def _append_row(self, doc: dict) -> None:
        """One manifest line, single O_APPEND write (concurrent writers
        interleave at line granularity; a torn tail is reader-skipped).
        Best-effort: a full disk must not fail the launch path."""
        doc = dict(doc)
        doc["t"] = time.time()
        try:
            with open(self._manifest(), "a") as f:
                f.write(json.dumps(doc, sort_keys=True,
                                   allow_nan=False) + "\n")
        except OSError:
            pass

    def record(self, key: str, spec: WarmupSpec, platform: str | None,
               donate: bool, device_kind: str) -> bool:
        """Persist one ladder entry: payload written tmp+rename (two
        racing writers rename identical content — last wins), then one
        ``write`` manifest row carrying the payload sha256 (what
        ``ckcache --verify`` re-hashes)."""
        if not self.enabled:
            return False
        self.arm()
        payload = json.dumps({
            "schema": SCHEMA,
            "key": key,
            "spec": spec.to_payload(),
            "platform": platform or "",
            "donate": bool(donate),
            "device_kind": device_kind,
        }, sort_keys=True, indent=0, allow_nan=False).encode()
        edir = self._entries_dir()
        path = os.path.join(edir, key + ".json")
        try:
            os.makedirs(edir, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=edir, prefix=".tmp-" + key)
            try:
                os.write(fd, payload)
            finally:
                os.close(fd)
            os.replace(tmp, path)
        except OSError:
            return False
        self._seen.add(key)
        _M_WRITE.inc()
        self._append_row({
            "op": "write", "key": key,
            "sha": hashlib.sha256(payload).hexdigest(),
            "bytes": len(payload),
        })
        self.prune()
        return True

    # -- size cap ------------------------------------------------------------
    def _lru_files(self) -> list[tuple[float, int, str]]:
        """(mtime, bytes, path) of every cap-governed file (entry
        payloads; never the manifest, and never jax's own cache
        directory — module docstring)."""
        out = []
        for d in (self._entries_dir(),):
            try:
                names = os.listdir(d)
            except OSError:
                continue
            for name in names:
                p = os.path.join(d, name)
                try:
                    st = os.stat(p)
                except OSError:
                    continue
                if os.path.isfile(p):
                    out.append((st.st_mtime, st.st_size, p))
        return sorted(out)

    def total_bytes(self) -> int:
        return sum(b for _t, b, _p in self._lru_files())

    def prune(self, max_bytes: int | None = None) -> int:
        """Evict oldest-mtime files until under the cap.  Returns the
        eviction count; each removal appends an ``evict`` row."""
        if not self.enabled:
            return 0
        cap = self.max_bytes() if max_bytes is None else int(max_bytes)
        files = self._lru_files()
        total = sum(b for _t, b, _p in files)
        evicted = 0
        for _t, b, p in files:
            if total <= cap:
                break
            try:
                os.remove(p)
            except OSError:
                continue
            total -= b
            evicted += 1
            _M_EVICT.inc()
            self._append_row({
                "op": "evict", "key": os.path.basename(p), "bytes": b})
        return evicted

    # -- operator views ------------------------------------------------------
    def stats(self) -> dict:
        """Entries/bytes on disk + hit/miss/write/evict totals from the
        manifest (cross-process totals — the in-process metric counters
        only see this interpreter)."""
        rows = self.manifest_rows()
        ops = {"hit": 0, "miss": 0, "write": 0, "evict": 0}
        for r in rows:
            op = r.get("op")
            if op in ops:
                ops[op] += 1
        edir = self._entries_dir()
        try:
            entries = sum(1 for n in os.listdir(edir) if n.endswith(".json"))
        except OSError:
            entries = 0
        return {
            "root": self.root,
            "entries": entries,
            "bytes": self.total_bytes(),
            "max_bytes": self.max_bytes(),
            **ops,
            "miss_reasons": dict(self.miss_reasons),
        }

    def verify(self) -> dict:
        """Re-hash every entry against its newest ``write`` manifest row.
        Returns ``{"ok": [...], "corrupt": [...], "unindexed": [...]}``
        — ``unindexed`` (entry present, write row torn away) is legal
        degraded state, reported so an operator can re-warm."""
        want: dict[str, str] = {}
        for r in self.manifest_rows():
            if r.get("op") == "write" and "sha" in r:
                want[str(r.get("key"))] = str(r["sha"])
        ok: list[str] = []
        corrupt: list[str] = []
        unindexed: list[str] = []
        edir = self._entries_dir()
        try:
            names = sorted(os.listdir(edir))
        except OSError:
            names = []
        for name in names:
            if not name.endswith(".json"):
                continue
            key = name[:-len(".json")]
            try:
                with open(os.path.join(edir, name), "rb") as f:
                    payload = f.read()
                json.loads(payload.decode())
            except Exception:  # noqa: BLE001 - unreadable = corrupt
                corrupt.append(key)
                continue
            sha = hashlib.sha256(payload).hexdigest()
            if key not in want:
                unindexed.append(key)
            elif want[key] == sha:
                ok.append(key)
            else:
                corrupt.append(key)
        return {"ok": ok, "corrupt": corrupt, "unindexed": unindexed}


#: Process singleton: root re-resolves from ``CK_COMPILE_CACHE`` per
#: operation, so tests and operators arm/disarm via the environment.
CACHE = CompileCache()


def warm_targets(workers) -> list:
    """Distinct (platform, donate, device_kind, device) combinations
    across a scheduler's lanes — the set of fused-launcher key variants
    the live path can request.  ``donate`` is the lane's own
    ``Worker.fused_donate``: a warmed key that differs in any component
    is a silent no-op."""
    seen: dict = {}
    for w in workers:
        platform = w.device.platform
        donate = w.fused_donate
        kind = str(getattr(w.device, "device_kind", platform))
        seen.setdefault((platform, donate, kind), w.device)
    return [(p, d, k, dev) for (p, d, k), dev in seen.items()]


def warmup(program, workers, plan) -> dict:
    """AOT-precompile a workload plan's full predicated launch ladders
    BEFORE traffic arrives (``Cores.warmup``: ``ServeFrontend.warmup``,
    the fabric's warm-on-join, and the elastic rejoin all route here).

    ``plan`` is an iterable of :class:`WarmupSpec` (or anything with the
    job surface ``kernels/params/global_range/local_range/values`` — e.g.
    ``serve.ServeJob``; live params are read for size/dtype only, NEVER
    executed against).  Per distinct spec, per distinct lane (platform,
    donate) variant, this builds and EXECUTES on scratch buffers:

    - the fused predicated-ladder executable under the EXACT key the
      live fused window requests (``KernelProgram.fused_launcher``
      9-tuple — executing it also fills jax's in-process dispatch
      cache, so the first live call is a cache hit end to end), and
    - every per-call chunk launcher ``step·2^k`` up to the global
      range (any balancer split's per-lane ladder is a subset).

    With ``CK_COMPILE_CACHE`` armed, each spec's ladder key is looked up
    in the on-disk manifest (hit/miss counted + ``ck_compile_cache_*``
    metrics), misses are persisted for other processes, and the XLA
    compiles triggered here are served from / written to JAX's
    persistent compilation cache — a joining shard warms from disk
    instead of recompiling.  Unarmed, the disk layer is skipped entirely
    and results stay bit-identical.

    Emits one ``cache-warmup`` flight event + context decision per plan
    (key set, hit/miss split, wall).  Returns ``{"warmed", "hits",
    "misses", "skipped", "wall_s", "kinds"}``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    t0 = time.perf_counter()
    if CACHE.enabled:
        CACHE.arm()
    specs: list = []
    seen_specs: set = set()
    skipped = 0
    for item in plan:
        if isinstance(item, WarmupSpec):
            spec = item
        else:
            try:
                spec = WarmupSpec.from_job(
                    item.kernels, item.params,
                    getattr(item, "compute_id", 0), item.global_range,
                    item.local_range,
                    getattr(item, "global_offset", 0),
                    getattr(item, "values", ()),
                )
            except Exception:  # noqa: BLE001 - unwarmable job shape
                skipped += 1
                continue
        ident = (spec.kernels, spec.params, spec.global_range,
                 spec.local_range, spec.values)
        if ident in seen_specs:
            continue
        seen_specs.add(ident)
        if (spec.local_range <= 0
                or spec.global_range % spec.local_range != 0
                or not all(n in program for n in spec.kernels)):
            skipped += 1
            continue
        specs.append(spec)

    hits = misses = 0
    keys: list[str] = []
    # per-device-kind ladder count: the mixed-fleet warmup proof —
    # every kind present in the lane set gets its own AOT pass
    kinds: dict[str, int] = {}
    for spec in specs:
        step = spec.local_range
        units = spec.global_range // step
        vals = spec.value_args()

        def vals_for(name, _v=vals):
            if isinstance(_v, dict):
                return tuple(_v.get(name, ()))
            return tuple(_v)

        for platform, donate, device_kind, device in warm_targets(workers):
            kinds[device_kind] = kinds.get(device_kind, 0) + 1
            key = None
            hit = False
            if CACHE.enabled:
                key = CACHE.ladder_key(
                    program, spec, platform, donate, device_kind)
                keys.append(key)
                hit = CACHE.lookup(key)
            bufs = tuple(
                jnp.zeros(n, dtype=np.dtype(d), device=device)
                for n, d in spec.params
            )
            # the fused predicated ladder, under the live path's key
            fn = program.fused_launcher(
                tuple(spec.kernels), step, spec.global_range,
                spec.local_range, spec.global_range, vals,
                platform=platform, donate=donate,
            )
            if fn is not None:
                # the run-time scalars as the live path hands them
                # over (Worker.ladder_scalars): int32 arrays on the
                # lane's device, the argument types of the executable
                out = fn(*(jax.device_put(np.int32(v), device)
                           for v in (0, units, 1)), bufs)
                jax.block_until_ready(out)
                bufs = tuple(out)  # donate consumed the scratch set
            # every per-call chunk the binary ladder can emit
            nbits = max(1, units.bit_length())
            for name in dict.fromkeys(spec.kernels):
                n_arr = program.array_param_count(name)
                va = vals_for(name)
                for k in range(nbits):
                    chunk = step << k
                    if chunk > spec.global_range:
                        break
                    try:
                        f2, _info = program.launcher(
                            name, chunk, spec.local_range,
                            spec.global_range, platform)
                        jax.block_until_ready(
                            f2(0, bufs[:n_arr], va))
                    except TypeError:
                        break  # unhashable static values: skip name
            if CACHE.enabled:
                if hit:
                    hits += 1
                else:
                    misses += 1
                    CACHE.record(key, spec, platform, donate,
                                 device_kind)
    wall_s = time.perf_counter() - t0
    FLIGHT.event(
        "cache-warmup", warmed=len(specs), hits=hits, misses=misses,
        skipped=skipped, wall_ms=round(wall_s * 1e3, 3),
        cache=CACHE.enabled, kinds=dict(kinds),
    )
    if DECISIONS.enabled:
        # context record (reads the filesystem: provenance, not
        # oracle) — which keys this plan warmed, from which split
        DECISIONS.record("cache-warmup", {
            "specs": [s.to_payload() for s in specs],
            "cache_enabled": CACHE.enabled,
            "cache_root": CACHE.root,
        }, {
            "warmed": len(specs), "hits": hits, "misses": misses,
            "skipped": skipped, "keys": keys,
            "wall_ms": round(wall_s * 1e3, 3),
            "kinds": dict(kinds),
        })
    return {"warmed": len(specs), "hits": hits, "misses": misses,
            "skipped": skipped, "wall_s": wall_s,
            "kinds": dict(kinds)}


def record_engaged(program, workers, run) -> None:
    """Persist an engaged fused window's ladder spec so OTHER processes
    can warm it from disk (the fleet's live signature mix IS the cache's
    content).  ``run`` carries ``kernel_names``, ``params``,
    ``compute_id``, ``global_range``, ``local_range``, ``value_args``.
    Cold path — once per distinct key per process (the ``_seen`` set
    bounds disk probes); best-effort and torn-tolerant like every cache
    write."""
    try:
        spec = WarmupSpec.from_job(
            run.kernel_names, run.params, run.compute_id,
            run.global_range, run.local_range, 0, run.value_args)
        for platform, donate, device_kind, _dev in warm_targets(workers):
            key = CACHE.ladder_key(
                program, spec, platform, donate, device_kind)
            if key in CACHE._seen:
                continue
            if not CACHE.lookup(key, count=False):
                CACHE.record(key, spec, platform, donate, device_kind)
    except Exception:  # noqa: BLE001 - cache is never load-bearing
        pass


def warm_from_disk(cores, cache: CompileCache | None = None) -> dict:
    """Warm a :class:`~cekirdekler_tpu.core.cores.Cores` from the
    persisted fleet signature mix: load every well-formed spec whose
    kernels the cores' program actually contains, and run
    ``Cores.warmup`` over them (each XLA compile is then served from
    jax's persistent compilation cache).  A disabled cache, an empty cache, and corrupt
    entries all degrade to ``{"warmed": 0, ...}`` — never an
    exception."""
    cache = CACHE if cache is None else cache
    if not cache.enabled:
        return {"warmed": 0, "hits": 0, "misses": 0, "skipped": 0,
                "wall_s": 0.0}
    cache.arm()
    specs = []
    skipped = 0
    for _key, spec in cache.load_specs():
        if all(name in cores.program for name in spec.kernels):
            specs.append(spec)
        else:
            skipped += 1
    out = cores.warmup(specs)
    out["skipped"] = skipped
    return out
