#!/usr/bin/env python
"""Persistent executable cache operator CLI (``core/compilecache.py``).

Subcommands over a cache root (``--root`` or ``CK_COMPILE_CACHE``):

- ``ls`` — one line per ladder entry: key, kernels, ladder geometry
  (``plan_signature`` blocks), operand bytes, platform/device kind,
  entry mtime.
- ``stats`` — entries + bytes on disk and the cross-process
  hit/miss/write/evict totals read back from ``manifest.jsonl`` (the
  in-process ``ck_compile_cache_*`` counters only see one interpreter;
  the manifest sees the fleet).
- ``prune`` — evict ``entries/`` files to the size cap (``--max-mb`` or
  ``CK_COMPILE_CACHE_MAX_MB``), oldest mtime first (hits refresh mtime),
  one ``evict`` manifest row per removal.  jax's own cache directory is
  not this tool's: where the package placed it (``<checkout>/.jax_cache``)
  building a ``Cores`` trims it to the same cap; one placed from outside
  belongs to whoever placed it.
- ``--verify`` (flag on any subcommand, or alone) — re-hash every entry
  payload against its newest ``write`` manifest row: ``corrupt``
  entries fail the exit code; ``unindexed`` ones (payload present, its
  write row torn away) are legal degraded state, reported only.

Torn manifest rows and unparsable payloads are skipped with named
reasons, never raised — the CLI inspects exactly the degraded states
the cache is designed to survive.

Usage::

    python tools/ckcache.py ls [--root DIR]
    python tools/ckcache.py stats [--root DIR] [--json]
    python tools/ckcache.py prune [--root DIR] [--max-mb N]
    python tools/ckcache.py --verify [--root DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # standalone `python tools/ckcache.py`
    sys.path.insert(0, REPO)

from cekirdekler_tpu.core.compilecache import (  # noqa: E402
    CACHE_ENV,
    CompileCache,
)
from cekirdekler_tpu.core.stream import plan_signature  # noqa: E402


def _cache(args) -> CompileCache | None:
    root = args.root or os.environ.get(CACHE_ENV, "").strip()
    if not root:
        print("no cache root: pass --root or set " + CACHE_ENV,
              file=sys.stderr)
        return None
    return CompileCache(root=root)


def cmd_ls(cache: CompileCache) -> int:
    rows = cache.load_specs()
    edir = os.path.join(cache.root, "entries")
    for key, spec in rows:
        path = os.path.join(edir, key + ".json")
        try:
            st = os.stat(path)
            size, mtime = st.st_size, st.st_mtime
        except OSError:
            size, mtime = 0, 0.0
        age = time.strftime("%Y-%m-%d %H:%M:%S", time.localtime(mtime))
        blocks = plan_signature(spec.ladder())
        obytes = sum(n * 4 for n, _d in spec.params)  # order-of-magnitude
        print(f"{key}  {'+'.join(spec.kernels):<24} "
              f"blocks={blocks:<24} operands~{obytes}B "
              f"entry={size}B  {age}")
    degraded = cache.miss_reasons.get("corrupt-entry", 0)
    print(f"{len(rows)} entries"
          + (f"  ({degraded} corrupt skipped)" if degraded else ""))
    return 0


def cmd_stats(cache: CompileCache, as_json: bool) -> int:
    s = cache.stats()
    if as_json:
        print(json.dumps(s, sort_keys=True, allow_nan=False))
        return 0
    print(f"root     {s['root']}")
    print(f"entries  {s['entries']}")
    print(f"bytes    {s['bytes']} / cap {s['max_bytes']}")
    print(f"hits     {s['hit']}")
    print(f"misses   {s['miss']}")
    print(f"writes   {s['write']}")
    print(f"evicts   {s['evict']}")
    if s["miss_reasons"]:
        print(f"degraded {s['miss_reasons']}")
    return 0


def cmd_prune(cache: CompileCache, max_mb: float | None) -> int:
    cap = None if max_mb is None else int(max_mb * (1 << 20))
    before = cache.total_bytes()
    evicted = cache.prune(cap)
    print(f"evicted {evicted} files "
          f"({before} -> {cache.total_bytes()} bytes)")
    return 0


def cmd_verify(cache: CompileCache) -> int:
    v = cache.verify()
    print(f"ok {len(v['ok'])}  corrupt {len(v['corrupt'])}  "
          f"unindexed {len(v['unindexed'])}")
    for key in v["corrupt"]:
        print(f"CORRUPT  {key}")
    for key in v["unindexed"]:
        print(f"unindexed {key}")
    return 1 if v["corrupt"] else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="ckcache", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("cmd", nargs="?", default="stats",
                    choices=("ls", "stats", "prune"))
    ap.add_argument("--root", default=None,
                    help=f"cache root (default ${CACHE_ENV})")
    ap.add_argument("--json", action="store_true",
                    help="stats as one JSON line")
    ap.add_argument("--max-mb", type=float, default=None,
                    help="prune cap override (default "
                         "$CK_COMPILE_CACHE_MAX_MB)")
    ap.add_argument("--verify", action="store_true",
                    help="re-hash entries against the manifest; "
                         "corrupt entries fail the exit code")
    args = ap.parse_args(argv)
    cache = _cache(args)
    if cache is None:
        return 2
    rc = 0
    if args.cmd == "ls":
        rc = cmd_ls(cache)
    elif args.cmd == "prune":
        rc = cmd_prune(cache, args.max_mb)
    elif not args.verify or args.cmd == "stats":
        rc = cmd_stats(cache, args.json)
    if args.verify:
        rc = max(rc, cmd_verify(cache))
    return rc


if __name__ == "__main__":
    sys.exit(main())
