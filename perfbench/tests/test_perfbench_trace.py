"""The reduction from a profiler trace to busy time, idle share, kernel
time and idle gaps: on a hand-made trace whose answers are counted by
hand, and on a small trace recorded on the chip."""

import gzip
import json
import os

import pytest

from perfbench.harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


def _hand_trace():
    """A 100 ms window.  Device 0 runs two programs, [10, 40) and
    [50, 90) ms; the first has a kernel [12, 30) and a copy [30, 35), the
    second a kernel [50, 70) overlapping a fusion [60, 80) and a kernel
    [80, 90).  An op straddles the window's start.  Device 1 is busy
    [0, 50) ms.  The host waits [40, 50) and sleeps [90, 100)."""
    dev0 = {"name": "/device:TPU:0", "lines": [
        {"name": trace.MODULES_LINE, "events": [
            ["jit_program", 10 * MS, 30 * MS],
            ["jit_program", 50 * MS, 40 * MS]]},
        {"name": trace.OPS_LINE, "events": [
            ["copy.1", -5 * MS, 7 * MS],
            ["conv2d_ws.3 s8[8,8,8,128] tpu_custom_call", 12 * MS, 18 * MS],
            ["copy.2", 30 * MS, 5 * MS],
            ["conv2d_ws_pipe.4 s8[8,8,8,128] tpu_custom_call", 50 * MS, 20 * MS],
            ["fusion.4", 60 * MS, 20 * MS],
            ["matmul_ws.1 s32[8,10] tpu_custom_call", 80 * MS, 10 * MS]]}]}
    dev1 = {"name": "/device:TPU:1", "lines": [
        {"name": trace.OPS_LINE, "events": [["fusion.9", 0, 50 * MS]]}]}
    host = {"name": "/host:CPU", "lines": [
        {"name": "main", "events": [
            [trace.WINDOW, 0, 100 * MS],
            ["bench.wait_result", 40 * MS, 10 * MS],
            ["bench.sleep", 90 * MS, 10 * MS]]}]}
    return {"planes": [host, dev0, dev1]}


def test_hand_made_trace():
    r = trace.reduce(_hand_trace())
    assert r.window_s == pytest.approx(0.1)
    assert r.devices == 2
    # device 0: [0,2) + [12,35) + [50,90) = 65 ms; device 1: 50 ms
    assert r.busy_s == pytest.approx((0.065 + 0.050) / 2)
    assert r.idle_share == pytest.approx(1 - 0.0575 / 0.1)
    assert r.kernel_s == pytest.approx(0.018 + 0.020 + 0.010)
    assert (r.runs, r.run_kernel_s) == (2, pytest.approx(0.048))
    gaps = dict(r.idle_gaps)
    # device 0: [2, 12), [35, 50), [90, 100); device 1: [50, 100)
    assert sorted(gaps.values()) == pytest.approx([0.01, 0.01, 0.015, 0.05])
    assert gaps["no host span (device 0, +2.000 ms)"] == pytest.approx(0.01)
    waits = [g for name, g in r.idle_gaps
             if name.startswith("bench.wait_result (device 0")]
    assert waits == [pytest.approx(0.015)]                 # [35, 50)
    assert any(name.startswith("bench.sleep (device 0") and
               g == pytest.approx(0.010) for name, g in r.idle_gaps)
    top = dict(r.device_ops)
    assert top["fusion.9"] == pytest.approx(0.050)
    assert top["copy.1"] == pytest.approx(0.002)            # clipped


def test_short_name_marks_pallas_kernels():
    hlo = ('%conv2d_ws_pipe.14 = s8[32,112,112,128]{3,2,1,0:T(8,128)} '
           'custom-call(s8[32,226,232,128]{3,2,1,0} %pad.37), '
           'custom_call_target="tpu_custom_call", kernel_metadata={}')
    assert trace.short_name(hlo) == \
        "conv2d_ws_pipe.14 s8[32,112,112,128] tpu_custom_call"
    assert trace.is_kernel(trace.short_name(hlo))
    copy = "%copy.6 = f32[32,224,224,3]{3,2,1,0:T(8,128)} copy(f32[32])"
    assert trace.short_name(copy) == "copy.6 f32[32,224,224,3]"
    assert not trace.is_kernel(trace.short_name(copy))


def test_trace_without_window_is_refused():
    tr = _hand_trace()
    tr["planes"][0]["lines"][0]["events"].pop(0)
    with pytest.raises(LookupError):
        trace.reduce(tr)


def _recorded():
    with gzip.open(os.path.join(DATA, "trace_vgg16_offline.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_recorded_chip_trace():
    """A slice of a traced ``vgg16.offline-b32`` run on a TPU v5e: the
    window, its device operations and the harness's host spans."""
    r = trace.reduce(_recorded())
    assert r.devices == 1
    assert 0 < r.kernel_s <= r.busy_s <= r.window_s
    assert r.runs >= 1 and 0 < r.run_kernel_s <= r.kernel_s
    names = [name for name, _ in r.device_ops]
    assert any(trace.is_kernel(n) for n in names)
    assert len(r.idle_gaps) <= 10 and len(r.device_ops) <= 10
