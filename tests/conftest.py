"""Test rig: 8 virtual CPU devices so multi-chip scheduling, the load
balancer, pipelines, and sharding are all testable without TPU hardware —
the fake-backend capability the reference lacks (SURVEY.md §4).

The rig is set up here, before anything imports jax: the CPU platform and
the 8-device flag are environment the backend reads once at its first
initialization.  ``pytest_configure`` then asserts the rig came up — a
suite that runs on another backend measures nothing.
"""

import os
import pathlib
import sys

_N_DEVICES = 8
_COUNT_FLAG = "--xla_force_host_platform_device_count"

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(
    [f for f in os.environ.get("XLA_FLAGS", "").split()
     if not f.startswith(_COUNT_FLAG)]
    + [f"{_COUNT_FLAG}={_N_DEVICES}"])
os.environ.setdefault("JAX_ENABLE_X64", "1")
# jax's persistent compilation cache is off for the suite: it compiles
# thousands of cheap CPU executables, and hashing, looking up and writing
# them cost one tier-1 run 672 s against 488 s without (one pair on this
# container; the driver stops the run at 870 s).  It also keeps a run from
# depending on what an earlier run left in .jax_cache.  Tests of the cache
# itself start children that switch it back on (tools/coldstart.py,
# tests/test_compilecache.py).
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")

def pytest_configure(config):
    import jax

    jax.config.update("jax_enable_x64", True)
    # XLA's DEFAULT matmul precision may decompose f32 matmuls into bf16
    # passes; parity tests (sharded vs single-device) need true-f32 products
    # so rounding doesn't depend on how GSPMD partitions the contraction
    jax.config.update("jax_default_matmul_precision", "highest")

    assert jax.default_backend() == "cpu", (
        f"rig requires cpu default backend, got {jax.default_backend()}"
    )
    assert len(jax.devices()) >= _N_DEVICES, (
        f"virtual device rig failed to initialize: {len(jax.devices())} "
        f"devices (was jax imported before tests/conftest.py?)"
    )

    # dynamic lock-order witness (opt-in: CK_LOCK_WITNESS=1): wrap the
    # package's named locks, record actual acquisition orders during the
    # run, and cross-check them against tools/ckcheck's static graph at
    # session end (tests/_artifacts/lock_witness.json).  Disagreements
    # are a report, not a failure — see docs/STATIC_ANALYSIS.md.
    global _WITNESS
    if os.environ.get("CK_LOCK_WITNESS") == "1" and _WITNESS is None:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        if repo not in sys.path:
            sys.path.insert(0, repo)
        try:
            from tools.ckcheck.witness import install

            _WITNESS = install(os.path.join(repo, "cekirdekler_tpu"))
        except Exception as e:  # noqa: BLE001 - witness must never sink a run
            print(f"[ck-lock-witness] install failed: {e!r}", file=sys.stderr)


_WITNESS = None


def pytest_sessionfinish(session, exitstatus):
    global _WITNESS
    if _WITNESS is None:
        return
    try:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        from tools.ckcheck import lock_order_edges, scan_package

        pkg = scan_package(os.path.join(repo, "cekirdekler_tpu"))
        static = set(lock_order_edges(pkg))
        path = os.path.join(repo, "tests", "_artifacts", "lock_witness.json")
        _WITNESS.write_report(static, path)
        rep = _WITNESS.report(static)
        print(
            f"\n[ck-lock-witness] {len(rep['dynamic_edges'])} dynamic / "
            f"{len(rep['static_edges'])} static order edges; "
            f"{len(rep['dynamic_only'])} dynamic-only (static blind spots), "
            f"{len(rep['static_only'])} static-only (unexercised) "
            f"-> {path}"
        )
    except Exception as e:  # noqa: BLE001
        print(f"[ck-lock-witness] report failed: {e!r}", file=sys.stderr)
    finally:
        _WITNESS.uninstall()
        _WITNESS = None


import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    import jax

    return jax.devices("cpu")[:_N_DEVICES]


# -- the benchmark's own checks ride tier-1 ----------------------------------
# benchmark/checks/test_*.py guard the harness that judges every PR.  They
# find the harness through their own __file__, so each is collected here as
# a module of its own, under its own path: `--dist loadfile` then spreads
# them over workers, and a check file added later is found by the glob.

_HERE = pathlib.Path(__file__).resolve()
_CHECKS = _HERE.parent.parent / "benchmark" / "checks"


class _BenchmarkChecks(pytest.Collector):
    def collect(self):
        for path in sorted(_CHECKS.glob("test_*.py")):
            yield pytest.Module.from_parent(self, path=path)


def pytest_collect_file(file_path, parent):
    # once, when the tests/ directory itself is collected
    if file_path == _HERE:
        return _BenchmarkChecks.from_parent(parent, name="benchmark/checks")
    return None
