"""The reduction from a profiler trace to per-layer numbers."""

import glob
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import tracing  # noqa: E402

DEV = "/device:TPU:0"
OPS = "XLA Ops"
HOST = "/host:CPU"
# op events on the TPU are named by their HLO text
FLASH = ("%flash_attention.9 = bf16[192,1024,128] custom-call(s32[1024,1] %iota, "
         "bf16[192,1024,128] %b), custom_call_target=\"tpu_custom_call\"")
ERA = ("%closed_call.30 = (f32[1572864], f32[1572864]) custom-call(f32[4]{0:T(128)} "
       "%fusion.272, f32[4]{0:T(128)} %gte.1, f32[2]{0:T(128)} %gte.2, f32[1572864] "
       "%bitcast.288), custom_call_target=\"tpu_custom_call\"")


def ev(plane, line, name, t, d):
    return {"plane": plane, "line": line, "name": name, "t": t, "d": d}


def small_trace():
    us = 1000
    return [
        ev(HOST, "main", "bench.window", 100 * us, 1000 * us),
        ev(HOST, "main", "bench.result_wait", 100 * us, 700 * us),
        ev(HOST, "main", "bench.host_copy", 800 * us, 250 * us),
        # before the window: not counted
        ev(DEV, OPS, "fusion.1", 0, 50 * us),
        # a loop whose body holds two ops: busy 100..400
        ev(DEV, OPS, "while.3", 100 * us, 300 * us),
        ev(DEV, OPS, FLASH, 120 * us, 100 * us),
        ev(DEV, OPS, ERA, 250 * us, 40 * us),
        # idle 400..500, then busy 500..700, idle 700..1100 (host copy)
        ev(DEV, OPS, FLASH, 500 * us, 200 * us),
        # runs past the window's end: clipped at 1100
        ev(DEV, OPS, "fusion.2", 1090 * us, 30 * us),
        ev(DEV, "XLA Modules", "jit_run", 100 * us, 1000 * us),
    ]


def test_busy_kernels_and_gaps_by_hand():
    s = tracing.reduce(small_trace())
    assert s["window_s"] == pytest.approx(1e-3)
    # busy: 100..400, 500..700, 1090..1100 (the module line is skipped)
    assert s["busy_s"] == pytest.approx((300 + 200 + 10) * 1e-6)
    fa = s["kernels"]["flash_attention"]
    assert fa["count"] == 2 and fa["s"] == pytest.approx(300e-6)
    era = s["kernels"]["era_update"]
    assert era["count"] == 1 and era["s"] == pytest.approx(40e-6)
    ops = dict(s["device_ops"])
    # the loop's self time leaves out its body
    assert ops["while.3"] == pytest.approx(160e-6)
    assert ops["%flash_attention.9"] == pytest.approx(300e-6)
    assert ops["%closed_call.30"] == pytest.approx(40e-6)
    gaps = dict(s["idle_gaps"])
    # 400..500 lies in result_wait only; 700..1090 mostly in host_copy
    # (800..1050), the innermost event covering half of it
    assert gaps["bench.result_wait"] == pytest.approx(100e-6)
    assert gaps["bench.host_copy"] == pytest.approx(390e-6)


def test_union_of_two_chips_is_averaged():
    events = small_trace()
    events += [dict(e, plane="/device:TPU:1") for e in events if e["plane"] == DEV]
    events.append(ev("/device:TPU:1", OPS, "fusion.5", 420 * 1000, 60 * 1000))
    one = tracing.reduce(small_trace())
    two = tracing.reduce(events)
    assert two["devices"] == 2
    assert two["busy_s"] == pytest.approx(one["busy_s"] + 30e-6)
    assert two["kernels"]["flash_attention"]["count"] == 4


def test_no_window_span_is_an_error():
    with pytest.raises(RuntimeError):
        tracing.reduce([e for e in small_trace() if e["name"] != "bench.window"])


RECORDED = sorted(glob.glob(os.path.join(ROOT, "bench", "testdata", "*.json")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_chip_trace(path):
    """A slice of a trace recorded on a v5e chip, reduced, against the
    busy union and kernel sums computed here by brute force."""
    with open(path) as f:
        events = json.load(f)
    s = tracing.reduce(events)
    lo, hi = tracing.window(events)
    dev = tracing.device_planes(events)[0]
    ops = [e for e in events if e["plane"] == dev and e["line"] == OPS]
    busy = set()
    step = 1000   # 1 us grid
    for e in ops:
        a, b = max(e["t"], lo), min(e["t"] + e["d"], hi)
        busy.update(range(a // step, b // step))
    assert s["busy_s"] == pytest.approx(len(busy) * step / 1e9, rel=0.02)
    for k in ("flash_attention", "era_update"):
        calls = [e for e in ops if tracing.KERNELS[k].search(e["name"])]
        assert calls, k
        total = sum(min(e["t"] + e["d"], hi) - max(e["t"], lo) for e in calls
                    if e["t"] < hi and e["t"] + e["d"] > lo)
        assert s["kernels"][k]["s"] == pytest.approx(total / 1e9)
    assert 0 < s["busy_s"] <= s["window_s"]
