"""The benchmark's reduction from a trace to busy union, idle share, idle by
host span and per-operation time, held to two traces (CPU container, no chip:
``python3 -m pytest benchmark/checks/test_trace_reduction.py -q``).

``fixtures/synthetic.xspace.txt`` is a text-format XSpace written by hand
(microsecond numbers, two chips, overlapping and window-straddling
operations, nested host spans); every expected value below is worked out on
paper.  ``fixtures/nbody_8k_3calls.xplane.pb.gz`` was recorded on a TPU v5e
(my chip run, PR 23): three calls of the n = 8192 window, 50 launches each.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import xplane  # noqa: E402

FIX = os.path.join(HERE, "fixtures")
US = 1e-6


def test_opcode_of_hlo_text():
    assert xplane.op_label(
        "%copy.3 = f32[8]{0:T(1024)} copy(f32[8]{0:T(1024)} %buf.1)"
    ) == ("copy.3", "copy")
    assert xplane.op_label(
        "%fn.1 = (f32[64,128]{1,0:T(8,128)}, f32[64,128]{1,0:T(8,128)}) "
        "custom-call(s32[1,1]{1,0:T(1,128)} %bitcast.12)"
    ) == ("fn.1", "custom-call")
    assert xplane.op_label("%copy-start = (f32[8]{0:S(1)}, u32[]{:S(2)}) "
                           "copy-start(f32[8]{0} %sl.1)") == (
        "copy-start", "copy-start")
    assert xplane.op_label("not hlo") == ("not hlo", "")


def test_synthetic_trace_by_hand():
    r = xplane.reduce(xplane.load(os.path.join(FIX, "synthetic.xspace.txt")))
    # the window runs from the first bench/call to the end of the last
    assert r.t0 == pytest.approx(1000 * US) and r.t1 == pytest.approx(3000 * US)
    assert r.calls == 2
    # chip 0: 100 (copy, clipped) + 400 (two overlapping kernels) + 100 + 400
    # + 50 (copy, clipped at the end); chip 1: 1000 + 250
    assert r.busy_s[0] == pytest.approx(1050 * US)
    assert r.busy_s[1] == pytest.approx(1250 * US)
    assert r.idle_share(0) == pytest.approx(0.475)
    assert r.worst_chip == 0
    # idle goes to the innermost host span; 1900-2000 lies between two calls
    assert r.idle_by_span[0] == pytest.approx(
        {"bench/enqueue": 300 * US, "bench/barrier": 550 * US,
         xplane.OUTSIDE: 100 * US})
    assert r.idle_by_span[1] == pytest.approx(
        {"bench/enqueue": 500 * US, "bench/barrier": 250 * US})
    # per-operation time sums durations (overlap counted twice), clipped
    assert xplane.seconds_of(r, 0, "custom-call") == (
        pytest.approx(900 * US), 3)
    assert xplane.seconds_of(r, 0, "copy") == (pytest.approx(150 * US), 1)
    assert xplane.seconds_of(r, 1, "custom-call") == (
        pytest.approx(1250 * US), 2)
    top = xplane.breakdown(r)
    assert top["device_ops"][0] == ["fn.1/custom-call",
                                    pytest.approx(2150 * US)]
    assert top["idle_gaps"][0] == ["bench/barrier", pytest.approx(550 * US)]


def test_recorded_v5e_trace():
    r = xplane.reduce(xplane.load(
        os.path.join(FIX, "nbody_8k_3calls.xplane.pb.gz")))
    assert r.calls == 3 and list(r.busy_s) == [0]
    assert r.window_s == pytest.approx(0.342752, abs=1e-6)
    assert r.busy_s[0] == pytest.approx(0.335827, abs=1e-6)
    seconds, launches = xplane.seconds_of(r, 0, "custom-call")
    assert launches == 150
    assert seconds / launches == pytest.approx(2.2372e-3, rel=1e-3)
    top = xplane.breakdown(r)
    assert top["device_ops"][0][0] == "fn.6/custom-call"
    assert len(top["device_ops"]) <= 10 and len(top["idle_gaps"]) <= 10
