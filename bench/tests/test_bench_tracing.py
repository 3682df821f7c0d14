"""The reduction from a profiler trace to busy time, idle gaps and
kernel time, on a hand-made trace and on a slice recorded on the chip."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench import tracing

DATA = Path(__file__).resolve().parent / "data"

# Times in ns.  Window 0-1000; ops overlap at 100-300; idle 300-400 while
# the host was in bench.observe, 600-1000 while it waited.
HAND = {
    "device_modules": [["jit_step(1)", 100, 200], ["jit_step(1)", 400, 200],
                       ["jit_late(2)", 1200, 10]],
    "device_ops": [["%fusion.1", 100, 150], ["%_ragged_pallas.3", 200, 100],
                   ["%_ragged_pallas.3", 400, 150], ["%copy.2", 550, 40],
                   ["%late", 1200, 10]],
    "host_spans": [["bench.window", 0, 1000], ["bench.step", 50, 250],
                   ["bench.observe", 300, 100], ["bench.step", 400, 200],
                   ["bench.wait", 600, 400]],
}


def test_hand_made_trace():
    r = tracing.reduce(HAND)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx(400e-9)        # 100-300, 400-600
    assert r.op_busy_s == pytest.approx(390e-9)     # 590-600 has no op
    assert r.idle_share == pytest.approx(0.6)
    assert r.time_of("ragged") == pytest.approx(250e-9)
    assert r.top_ops(2) == [["%_ragged_pallas.3", pytest.approx(250e-9)],
                            ["%fusion.1", pytest.approx(150e-9)]]
    assert [(n, round(g * 1e9), round(a * 1e9)) for n, g, a in r.gaps] == [
        ("bench.wait", 400, 600), ("bench.step", 100, 0),
        ("bench.observe", 100, 300)]


def test_no_window_span_uses_the_ops():
    t = {"device_modules": [["a", 10, 10], ["b", 40, 10]],
         "device_ops": [], "host_spans": []}
    r = tracing.reduce(t)
    assert r.window_s == pytest.approx(40e-9)
    assert r.busy_s == pytest.approx(20e-9)
    assert r.gaps == [("outside bench spans", pytest.approx(20e-9),
                       pytest.approx(10e-9))]


def _recorded():
    return json.loads((DATA / "trace_slice.json").read_text())


def test_recorded_slice_busy_matches_a_timeline():
    trace = _recorded()
    r = tracing.reduce(trace)
    (w0, wd), = [(s, d) for n, s, d in trace["host_spans"]
                 if n == tracing.WINDOW_SPAN]
    # brute force at 100 ns resolution
    n = int(wd // 100) + 1
    busy = np.zeros(n, bool)
    for _, s, d in trace["device_modules"]:
        a = int(max(s - w0, 0) // 100)
        b = int(min(s + d - w0, wd) // 100)
        if b > a:
            busy[a:b] = True
    assert r.busy_s == pytest.approx(busy.sum() * 100e-9, rel=2e-3)
    assert 0 < r.busy_s < r.window_s
    total_gaps = sum(g for _, g, _ in r.gaps)
    assert total_gaps == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
    assert all(name.startswith("bench.") or name == "outside bench spans"
               for name, _, _ in r.gaps)
    # every operation runs inside a program
    assert r.op_busy_s <= r.busy_s * (1 + 1e-9)


def test_recorded_slice_kernel_time():
    trace = _recorded()
    r = tracing.reduce(trace)
    (w0, wd), = [(s, d) for n, s, d in trace["host_spans"]
                 if n == tracing.WINDOW_SPAN]
    want = sum(min(s + d, w0 + wd) - max(s, w0)
               for n, s, d in trace["device_ops"]
               if "ragged" in n and s + d > w0 and s < w0 + wd) * 1e-9
    assert r.time_of("ragged") == pytest.approx(want)
    assert r.time_of("ragged") > 0
    top = r.top_ops(10)
    assert len(top) <= 10 and top == sorted(top, key=lambda x: -x[1])
