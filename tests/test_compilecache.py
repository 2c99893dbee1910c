"""Persistent executable cache + AOT warmup (`core/compilecache.py`,
ISSUE 18).

The pins, in the order the autoscale story needs them:

- **warmup = live key**: after `Cores.warmup` / `ServeFrontend.warmup`
  the FIRST live fused call compiles nothing (`fused_compiled_count`
  AND `compiled_count` flat) and warmup never touches the jobs' arrays
  (scratch buffers only).
- **cross-process**: process A populates the cache through the LIVE
  engage-time recorder; process B (a cold `tests/_cache_worker.py`
  interpreter) replays `warm_from_disk` and its first live batch
  compiles nothing — the kill-cold-start acceptance.
- **degradation**: torn manifest rows and corrupt entry payloads are
  NAMED misses, never exceptions; concurrent writers converge; an
  unset `CK_COMPILE_CACHE` and every miss path are bit-invisible
  (results pinned fused on AND off, cache off/on/warm).
- **operator surface**: `tools/ckcache.py` ls/stats/prune/--verify and
  the `tools/coldstart.py` cold/populate/warm trio smoke in-tree.
"""

import importlib.util
import json
import os
import subprocess
import sys
import threading
import types

import numpy as np
import pytest

from cekirdekler_tpu import ClArray
from cekirdekler_tpu.core import NumberCruncher
from cekirdekler_tpu.core.compilecache import (
    CACHE,
    CACHE_ENV,
    CACHE_MAX_MB_ENV,
    CompileCache,
    WarmupSpec,
    program_fingerprint,
    trim_placed_jax_cache,
    warm_from_disk,
)
from cekirdekler_tpu.hardware import platforms
from cekirdekler_tpu.serve import ServeFabric, ServeFrontend, ServeJob

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

SRC = """
__kernel void inc(__global float* a) {
    int i = get_global_id(0);
    a[i] = a[i] + 1.0f;
}
__kernel void dbl(__global float* a) {
    int i = get_global_id(0);
    a[i] = a[i] * 1.001f;
}
"""

N, LR = 1024, 64


def _load_tool(name, relpath):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def devs():
    return platforms().cpus()


@pytest.fixture()
def cache_root(tmp_path, monkeypatch):
    """Arm the process-wide CACHE singleton at a fresh root; disarm on
    teardown."""
    root = str(tmp_path / "cache")
    monkeypatch.setenv(CACHE_ENV, root)
    CACHE._seen.clear()
    CACHE.miss_reasons.clear()
    yield root
    _disarm()


def _disarm():
    CACHE._seen.clear()
    CACHE._armed_dir = None


# ---------------------------------------------------------------------------
# jax's own cache: place, floor and bound are configured once, at import
# ---------------------------------------------------------------------------

def test_jax_cache_placed_at_import_and_never_jax_evicted():
    import jax

    import cekirdekler_tpu

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        assert cekirdekler_tpu.PLACED_CACHE_DIR == os.path.join(
            ROOT, ".jax_cache")
        assert (jax.config.jax_compilation_cache_dir
                == cekirdekler_tpu.PLACED_CACHE_DIR)
    # jax's own eviction stays off: it re-reads a stamp per entry on every
    # write (core/compilecache.trim_placed_jax_cache bounds the directory)
    if "JAX_COMPILATION_CACHE_MAX_SIZE" not in os.environ:
        assert jax.config.jax_compilation_cache_max_size == -1
    # the suite itself runs without the cache (tests/conftest.py)
    assert not jax.config.jax_enable_compilation_cache


def test_placed_cache_is_trimmed_to_the_cap_oldest_written_first(
        tmp_path, monkeypatch):
    import cekirdekler_tpu

    d = tmp_path / "placed"
    d.mkdir()
    for i in range(5):  # 5 x 1000 bytes, oldest first
        f = d / f"jit_f-{i}-cache"
        f.write_bytes(b"x" * 1000)
        os.utime(f, (1_000_000 + i, 1_000_000 + i))
    (d / "notes.txt").write_bytes(b"y" * 5000)  # not an executable: kept
    monkeypatch.setattr(cekirdekler_tpu, "PLACED_CACHE_DIR", str(d))
    assert trim_placed_jax_cache(2500) == 3
    assert sorted(os.listdir(d)) == [
        "jit_f-3-cache", "jit_f-4-cache", "notes.txt"]
    assert trim_placed_jax_cache(2500) == 0  # under the cap: no-op
    # the default cap is the one the manifest's entries/ obey
    monkeypatch.setenv(CACHE_MAX_MB_ENV, "0")
    assert trim_placed_jax_cache() == 2
    # a directory that is not there yet, and one placed from outside
    monkeypatch.setattr(cekirdekler_tpu, "PLACED_CACHE_DIR",
                        str(tmp_path / "absent"))
    assert trim_placed_jax_cache(0) == 0
    monkeypatch.setattr(cekirdekler_tpu, "PLACED_CACHE_DIR", None)
    assert trim_placed_jax_cache(0) == 0


def test_jax_cache_placed_from_outside_in_a_fresh_process(tmp_path):
    """JAX_COMPILATION_CACHE_DIR decides the place: the package sets no
    other and deletes nothing in a directory it does not own; unpinned,
    every executable persists — a compile lands there."""
    place = str(tmp_path / "placed")
    env = os.environ.copy()
    env.update({"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": place})
    for k in ("JAX_ENABLE_COMPILATION_CACHE",
              "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
              "JAX_COMPILATION_CACHE_MAX_SIZE", CACHE_ENV):
        env.pop(k, None)
    code = (
        "import json, jax, cekirdekler_tpu\n"
        "import jax.numpy as jnp\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()\n"
        "c = jax.config\n"
        "print(json.dumps([c.jax_compilation_cache_dir,"
        " c.jax_persistent_cache_min_compile_time_secs,"
        " c.jax_compilation_cache_max_size]))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    got_dir, floor, cap = json.loads(out.stdout.strip().splitlines()[-1])
    assert got_dir == place and floor == 0 and cap == -1
    assert any(n.endswith("-cache") for n in os.listdir(place))


def _fused_batch(cr, arr, cid, iters, kernel="inc"):
    cr.enqueue_mode = True
    cr.cores.compute_fused_batch([kernel], [arr], cid, arr.size, LR, iters)
    cr.cores.barrier()
    cr.cores.flush()
    cr.enqueue_mode = False


def _spec(kernels=("inc",), n=N, lr=LR, values=()):
    return WarmupSpec(kernels=tuple(kernels), params=((n, "float32"),),
                      global_range=n, local_range=lr, values=values)


# ---------------------------------------------------------------------------
# warmup key = live key (the satellite-1 compile-counter pins)
# ---------------------------------------------------------------------------

def test_cores_warmup_then_first_live_fused_call_is_hit(devs, cache_root):
    cr = NumberCruncher(devs.subset(1), SRC)
    try:
        out = cr.cores.warmup([_spec()])
        assert out["warmed"] == 1 and out["skipped"] == 0
        assert out["misses"] == 1 and out["hits"] == 0  # cold cache
        prog = cr.cores.program
        before = (prog.fused_compiled_count, prog.compiled_count)
        assert before[0] >= 1  # warmup really built the ladder
        x = ClArray(np.zeros(N, np.float32), name="cw")
        x.partial_read = True
        _fused_batch(cr, x, 7300, 5)
        np.testing.assert_array_equal(np.asarray(x), 5.0)
        # the acceptance pin: the first live call after warmup compiles
        # NOTHING — neither the fused ladder nor a per-call chunk
        assert (prog.fused_compiled_count, prog.compiled_count) == before
        # and the warmed entry is now on disk for other processes
        cache = CompileCache(root=cache_root)
        assert len(cache.load_specs()) == 1
        assert cache.stats()["write"] >= 1
    finally:
        cr.dispose()


def test_cores_warmup_without_cache_env_still_precompiles(devs,
                                                          monkeypatch):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    assert not CACHE.enabled
    cr = NumberCruncher(devs.subset(1), SRC)
    try:
        out = cr.cores.warmup([_spec()])
        assert out["warmed"] == 1
        assert out["hits"] == 0 and out["misses"] == 0  # no cache layer
        prog = cr.cores.program
        before = (prog.fused_compiled_count, prog.compiled_count)
        x = ClArray(np.zeros(N, np.float32), name="nc")
        x.partial_read = True
        _fused_batch(cr, x, 7301, 4)
        np.testing.assert_array_equal(np.asarray(x), 4.0)
        assert (prog.fused_compiled_count, prog.compiled_count) == before
    finally:
        cr.dispose()


def test_frontend_warmup_matches_live_key_and_never_mutates(devs):
    cr = NumberCruncher(devs.subset(1), SRC)
    fe = ServeFrontend(cr, autostart=False, name="warmkeys")
    try:
        a = ClArray(np.zeros(N, np.float32), name="wk")
        a.partial_read = True
        job = ServeJob(params=[a], kernels=["inc"], compute_id=7302,
                       global_range=N, local_range=LR)
        out = fe.warmup([job])
        assert out["warmed"] == 1
        # scratch buffers only: the job's live array is untouched
        assert np.all(np.asarray(a) == 0.0)
        prog = cr.cores.program
        before = (prog.fused_compiled_count, prog.compiled_count)
        futs = [fe.submit("t0", job) for _ in range(8)]
        fe.step()
        for f in futs:
            f.result(timeout=30)
        np.testing.assert_array_equal(np.asarray(a), 8.0)
        assert (prog.fused_compiled_count, prog.compiled_count) == before
    finally:
        fe.close()
        cr.dispose()


def test_fabric_add_member_zero_fresh_compiles_when_cache_holds_mix(
        devs, cache_root):
    """The warm-on-join acceptance: live traffic persists the fleet's
    signature mix (engage-time recorder), so a joining member's warmup
    is ALL disk hits — zero fresh ladder compiles — and its first live
    batch after the join compiles nothing either."""
    crunchers = {m: NumberCruncher(devs.subset(1), SRC)
                 for m in ("m0", "m1")}
    fab = ServeFabric(crunchers, autostart=False, gather_window_s=0.0,
                      max_batch=64)
    a = ClArray(np.zeros(N, np.float32), name="fz")
    a.partial_read = True
    job = ServeJob(params=[a], kernels=["inc"], compute_id=9300,
                   global_range=N, local_range=LR)
    try:
        futs = [fab.submit("t0", job) for _ in range(6)]
        for _ in range(40):
            fab.step()
            if all(f.done() for f in futs):
                break
        assert np.all(np.asarray(a) == 6.0)
        cache = CompileCache(root=cache_root)
        assert cache.stats()["write"] >= 1  # the engage recorder fired
        before = cache.stats()
        fab.add_member("m2", NumberCruncher(devs.subset(1), SRC), step=1)
        after = cache.stats()
        assert after["miss"] == before["miss"]  # ZERO fresh compiles
        assert after["hit"] > before["hit"]
        # the joined shard's first live batch compiles nothing
        fe2 = fab.shards["m2"]
        prog2 = fe2.cores.program
        warmed = (prog2.fused_compiled_count, prog2.compiled_count)
        b = ClArray(np.zeros(N, np.float32), name="fz2")
        b.partial_read = True
        cr2 = fe2.cruncher
        cr2.enqueue_mode = True
        fe2.cores.compute_fused_batch(["inc"], [b], 9300, N, LR, 4)
        fe2.cores.barrier()
        fe2.cores.flush()
        cr2.enqueue_mode = False
        np.testing.assert_array_equal(np.asarray(b), 4.0)
        assert (prog2.fused_compiled_count, prog2.compiled_count) == warmed
    finally:
        fab.close()


# ---------------------------------------------------------------------------
# cross-process: populate cold, hit cold (tests/_cache_worker.py)
# ---------------------------------------------------------------------------

def _worker(env):
    return subprocess.Popen(
        [sys.executable, os.path.join(HERE, "_cache_worker.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, env=env)


def _rpc(proc, obj, timeout=120.0):
    proc.stdin.write(json.dumps(obj) + "\n")
    proc.stdin.flush()
    line = proc.stdout.readline()
    assert line, f"worker died: {proc.stderr.read()[-800:]}"
    return json.loads(line)


def test_cross_process_populate_then_cold_process_hits(cache_root):
    env = os.environ.copy()
    env[CACHE_ENV] = cache_root
    env.setdefault("JAX_PLATFORMS", "cpu")
    batch = {"op": "batch", "n": N, "lr": LR, "iters": 4, "scale": 1.0}
    a = _worker(env)
    try:
        ready = json.loads(a.stdout.readline())
        assert ready["op"] == "ready" and ready["cache"] is True
        done = _rpc(a, batch)
        assert done["op"] == "done"
        assert done["fused_compiles"] >= 1  # A was genuinely cold
        assert done["value"] == 4.0 and done["uniform"]
        stats = _rpc(a, {"op": "stats"})["stats"]
        assert stats["write"] >= 1 and stats["entries"] >= 1
        _rpc(a, {"op": "exit"})
    finally:
        a.kill()
        a.wait()
    b = _worker(env)
    try:
        assert json.loads(b.stdout.readline())["op"] == "ready"
        warmed = _rpc(b, {"op": "warm_disk"})
        assert warmed["warmed"] >= 1
        assert warmed["hits"] >= 1 and warmed["misses"] == 0
        done = _rpc(b, batch)
        # the kill-cold-start pin: B's first live batch compiles NOTHING
        assert done["fused_compiles"] == 0 and done["call_compiles"] == 0
        assert done["value"] == 4.0 and done["uniform"]  # bit-identical
        _rpc(b, {"op": "exit"})
    finally:
        b.kill()
        b.wait()


# ---------------------------------------------------------------------------
# degradation: torn rows, corrupt payloads, racing writers, LRU cap
# ---------------------------------------------------------------------------

def _fake_program():
    return types.SimpleNamespace(source=SRC, _py_kernels={})


def _record_n(cache, count):
    prog = _fake_program()
    keys = []
    for i in range(count):
        spec = _spec(n=N * (i + 1))
        key = cache.ladder_key(prog, spec, "cpu", False, "cpu")
        cache.record(key, spec, "cpu", False, "cpu")
        keys.append(key)
    return keys


def test_torn_manifest_row_and_corrupt_entry_are_named_misses(cache_root):
    cache = CompileCache(root=cache_root)
    keys = _record_n(cache, 2)
    rows = cache.manifest_rows()
    assert len(rows) == 2
    # a crashed writer's torn half-row: skipped with a named reason
    with open(cache._manifest(), "a") as f:
        f.write('{"op": "write", "key": "tor')
    assert len(cache.manifest_rows()) == 2  # parseable rows survive
    assert cache.stats()["entries"] == 2  # stats never raises
    # a corrupt entry payload: lookup degrades to a NAMED miss
    bad = os.path.join(cache._entries_dir(), keys[0] + ".json")
    with open(bad, "w") as f:
        f.write("{this is not json")
    assert cache.lookup(keys[0]) is False
    assert cache.miss_reasons.get("corrupt-entry", 0) >= 1
    assert cache.lookup(keys[1]) is True  # neighbors unharmed
    # load_specs skips the corrupt entry, returns the good one
    assert [k for k, _s in cache.load_specs()] == [keys[1]]
    # verify names the corrupt key
    v = cache.verify()
    assert keys[0] in v["corrupt"] and keys[1] in v["ok"]
    # an absent key is the OTHER named miss
    assert cache.lookup("0" * 32) is False
    assert cache.miss_reasons.get("absent", 0) >= 1


def test_concurrent_writers_converge(cache_root):
    cache = CompileCache(root=cache_root)
    prog = _fake_program()
    specs = [_spec(n=N * (i + 1)) for i in range(4)]
    keys = [cache.ladder_key(prog, s, "cpu", False, "cpu") for s in specs]
    errors = []

    def writer(tid):
        try:
            for _ in range(10):
                for key, spec in zip(keys, specs):
                    cache.record(key, spec, "cpu", False, "cpu")
        except Exception as exc:  # noqa: BLE001 - the failure under test
            errors.append(exc)

    threads = [threading.Thread(target=writer, args=(t,)) for t in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    # every entry is well-formed, every manifest row parseable
    assert sorted(k for k, _s in cache.load_specs()) == sorted(keys)
    assert cache.verify()["corrupt"] == []
    assert len(cache.manifest_rows()) >= 6 * 10 * len(keys)


def test_lru_prune_evicts_oldest_to_cap(cache_root):
    cache = CompileCache(root=cache_root)
    keys = _record_n(cache, 5)
    edir = cache._entries_dir()
    for i, key in enumerate(keys):  # deterministic LRU order
        os.utime(os.path.join(edir, key + ".json"), (1000 + i, 1000 + i))
    total = cache.total_bytes()
    assert total > 0
    evicted = cache.prune(max_bytes=total // 2)
    assert evicted >= 1
    assert cache.total_bytes() <= total // 2
    left = {k for k, _s in cache.load_specs()}
    assert keys[-1] in left and keys[0] not in left  # oldest went first
    assert cache.stats()["evict"] >= evicted
    assert os.path.exists(cache._manifest())  # the manifest never evicts


def test_spec_roundtrip_values_hashable_and_key_stable(cache_root):
    cache = CompileCache(root=cache_root)
    prog = _fake_program()
    job_param = types.SimpleNamespace(size=N, dtype="float32")
    spec = WarmupSpec.from_job(["inc"], [job_param], 7, N, LR, 0,
                               {"inc": (N, 0.0001)})
    rt = WarmupSpec.from_payload(json.loads(json.dumps(spec.to_payload())))
    assert rt == spec
    hash(rt)  # deep-frozen: dedup sets and dataclass hashing both work
    k1 = cache.ladder_key(prog, spec, "cpu", False, "cpu")
    k2 = cache.ladder_key(prog, rt, "cpu", False, "cpu")
    assert k1 == k2  # JSON round-trip cannot split the key
    # compute_id is a runtime scalar, never a key component
    other_cid = WarmupSpec.from_job(["inc"], [job_param], 99, N, LR, 0,
                                    {"inc": (N, 0.0001)})
    assert cache.ladder_key(prog, other_cid, "cpu", False, "cpu") == k1
    # a program-source change IS a key change
    prog2 = types.SimpleNamespace(source=SRC + "\n", _py_kernels={})
    assert cache.ladder_key(prog2, spec, "cpu", False, "cpu") != k1
    assert program_fingerprint(prog) != program_fingerprint(prog2)


def test_cache_is_bit_invisible_fused_on_and_off(devs, tmp_path,
                                                 monkeypatch):
    """The degradation acceptance: unset env, cold cache, warm cache —
    all bit-identical, on the fused path AND the per-call fallback
    (dbl's `*1.001f` makes any drift float-visible)."""
    root = str(tmp_path / "bitcache")
    rng = np.random.default_rng(7)
    seed = rng.standard_normal(N).astype(np.float32)
    images = {}
    for mode in ("env-off", "cache-cold", "cache-warm"):
        if mode == "env-off":
            monkeypatch.delenv(CACHE_ENV, raising=False)
        else:
            monkeypatch.setenv(CACHE_ENV, root)
        CACHE._seen.clear()
        for fused in (True, False):
            cr = NumberCruncher(devs.subset(1), SRC)
            try:
                if mode == "cache-warm":
                    warm_from_disk(cr.cores)
                cr.fused_dispatch = fused
                x = ClArray(seed.copy(), name=f"bi-{mode}-{fused}")
                x.partial_read = True
                _fused_batch(cr, x, 7400, 6, kernel="dbl")
                images[(mode, fused)] = np.asarray(x).copy()
            finally:
                cr.dispose()
    ref = images[("env-off", True)]
    for key, img in images.items():
        np.testing.assert_array_equal(img, ref, err_msg=str(key))
    _disarm()


# ---------------------------------------------------------------------------
# operator surface: tools/ckcache.py + tools/coldstart.py
# ---------------------------------------------------------------------------

ckcache = _load_tool("ck_cache_cli", "tools/ckcache.py")
coldstart = _load_tool("ck_coldstart_tool", "tools/coldstart.py")


def test_ckcache_cli_ls_stats_prune_verify(cache_root, capsys):
    cache = CompileCache(root=cache_root)
    keys = _record_n(cache, 3)
    assert ckcache.main(["ls", "--root", cache_root]) == 0
    assert "3 entries" in capsys.readouterr().out
    assert ckcache.main(["stats", "--root", cache_root, "--json"]) == 0
    stats = json.loads(capsys.readouterr().out)
    assert stats["entries"] == 3 and stats["write"] == 3
    assert ckcache.main(["--verify", "--root", cache_root]) == 0
    capsys.readouterr()
    # corrupt one entry: --verify fails the exit code and names it
    with open(os.path.join(cache._entries_dir(), keys[0] + ".json"),
              "w") as f:
        f.write("garbage")
    assert ckcache.main(["--verify", "--root", cache_root]) == 1
    assert keys[0] in capsys.readouterr().out
    # prune to zero cap: everything LRU-evicts, stats still works
    assert ckcache.main(["prune", "--root", cache_root,
                         "--max-mb", "0"]) == 0
    capsys.readouterr()
    assert ckcache.main(["stats", "--root", cache_root, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["entries"] == 0


def test_ckcache_cli_without_root_exits_2(monkeypatch, capsys):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    assert ckcache.main(["stats"]) == 2
    capsys.readouterr()


def test_coldstart_trio_smoke(tmp_path):
    """The bench section's unit: tiny cold/populate/warm subprocess trio
    — exactness and the warm child's all-hits warmup are deterministic
    pins; the speedup magnitude is the bench's job, not this test's."""
    out = coldstart._trio("nbody", str(tmp_path), 512, 64, 2, 64)
    assert out["cold"].get("error") is None
    assert out["warm"].get("error") is None
    assert out["exact"] is True
    assert out["warm"]["warm"]["hits"] >= 1
    assert out["warm"]["warm"]["misses"] == 0
    assert out["warm_speedup"] is not None and out["warm_speedup"] > 0


def test_coldstart_section_shape(tmp_path):
    """coldstart_section carries the headline key + the resilience
    rider without re-running anything resilience-shaped."""
    sec = coldstart.coldstart_section(
        None, resilience={"rejoin_converge_iters": 3, "exact": True},
        n=512, local_range=64, iters=2, include_flash=False,
        cache_root=str(tmp_path))
    assert sec["rejoin_converge_iters"] == 3
    assert "cold_start_warm_speedup" in sec
    assert sec["flash"] == {"skipped": "disabled"}
