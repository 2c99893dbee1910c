"""End-to-end CLI coverage for the profiling tools (ISSUE 8):

- ``tools/profile_gap.py`` — rewritten in r7 on top of the trace
  subsystem but never exercised as a CLI until now: the layer-peeling
  run must print the attribution tables and the ``--chrome`` dump must
  parse back through the Chrome-trace reader.
- ``tools/kernel_profile.py`` — the device-profile CLI: run mode on
  the CPU rig (named absence + unified trace), ``--trace-dir`` mode on
  a synthetic-Xprof fixture (full per-kernel table + roofline), and
  the ``--store`` / ``--show-store`` persistence loop.

Subprocess invocations inherit the rig env (JAX_PLATFORMS=cpu) so the
children run on the same virtual-device rig as the suite.
"""

import gzip
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _run(tool, *args, timeout=240):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", tool), *args],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_profile_gap_cli_attribution_and_chrome_dump(tmp_path):
    chrome = str(tmp_path / "gap.json")
    r = _run("profile_gap.py", "--size", "64", "--iters", "1",
             "--chrome", chrome)
    assert r.returncode == 0, r.stdout + r.stderr
    out = r.stdout
    # every layer printed its stopwatch line...
    for label in ("tuned pallas loop", "direct launcher fn",
                  "framework compute() enqueue",
                  "framework no_compute (sched only)"):
        assert label in out, f"missing segment {label!r}:\n{out}"
    # ...and the traced segments printed the attribution table
    assert out.count("-- attribution") == 2
    assert "wall" in out and "span-covered" in out and "gap" in out
    assert "kind" in out and "% wall" in out
    # the chrome dump parses back through the pinned reader with spans
    from cekirdekler_tpu.trace.export import from_chrome_trace

    doc = json.load(open(chrome))
    spans = from_chrome_trace(doc)
    assert spans, "chrome dump round-tripped to zero spans"
    assert {"launch", "fence"} & {s.kind for s in spans}


def _fixture_dump(dirpath):
    os.makedirs(dirpath, exist_ok=True)
    events = [
        {"ph": "M", "name": "process_name", "pid": 7,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "thread_name", "pid": 7, "tid": 2,
         "args": {"name": "XLA Ops"}},
        {"ph": "X", "pid": 1, "tid": 0, "ts": 0.0, "dur": 40.0,
         "name": "ck/launch",
         "args": {"kernel": "mandelbrot", "cid": "7", "lane": "0",
                  "seq": "1"}},
        {"ph": "X", "pid": 7, "tid": 2, "ts": 100.0, "dur": 5000.0,
         "name": "fusion.1", "args": {"ck-seq": 1}},
        {"ph": "X", "pid": 7, "tid": 2, "ts": 5300.0, "dur": 700.0,
         "name": "fusion.2", "args": {"ck-seq": 1}},
    ]
    with gzip.open(os.path.join(dirpath, "h.trace.json.gz"), "wt") as f:
        json.dump({"traceEvents": events}, f)


def test_kernel_profile_cli_trace_dir_roofline_and_store(tmp_path):
    fix = str(tmp_path / "fix")
    store = str(tmp_path / "store")
    _fixture_dump(fix)
    # an offline dump on the CPU rig: the roof is the dump's chip's, and
    # the caller names it — no peak is assumed for an unknown device
    r = _run("kernel_profile.py", "--trace-dir", fix, "--store", store,
             "--flops", "1e9", "--bytes", "1e8",
             "--peak-tflops", "197", "--peak-gbps", "819")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "mandelbrot" in r.stdout and "device ms" in r.stdout
    assert "5.700" in r.stdout          # 5.0 + 0.7 ms attributed
    assert "roofline mandelbrot" in r.stdout
    assert "memory-bound" in r.stdout or "compute-bound" in r.stdout
    assert os.listdir(store), "--store persisted nothing"

    s = _run("kernel_profile.py", "--show-store", "--store", store)
    assert s.returncode == 0, s.stdout + s.stderr
    assert "1 key(s)" in s.stdout and "device_ms=5.7" in s.stdout


def test_kernel_profile_cli_json_report(tmp_path):
    fix = str(tmp_path / "fix")
    _fixture_dump(fix)
    r = _run("kernel_profile.py", "--trace-dir", fix, "--json")
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["kernels"][0]["kernel"] == "mandelbrot"
    assert doc["coverage_frac"] == 1.0


def test_kernel_profile_cli_run_mode_named_absence_on_cpu(tmp_path):
    """Run mode on the CPU rig: the capture machinery runs end-to-end
    and the report degrades to a NAMED absence (no device tracks) with
    a unified chrome dump that still carries the host spans."""
    chrome = str(tmp_path / "uni.json")
    r = _run("kernel_profile.py", "--size", "64", "--iters", "1",
             "--capture-dir", str(tmp_path / "cap"), "--chrome", chrome)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "device profile absent" in r.stdout or "coverage" in r.stdout
    from cekirdekler_tpu.trace.device import split_unified_trace

    spans, ops = split_unified_trace(json.load(open(chrome)))
    assert spans, "unified dump lost the host spans"


def test_kernel_profile_cli_show_store_without_root():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("CK_PROFILE_STORE", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "kernel_profile.py"),
         "--show-store"],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert r.returncode == 1
    assert "no store configured" in r.stderr
