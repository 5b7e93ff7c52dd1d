"""The reduction from a profiler trace to busy time, idle gaps and top
operations, on a recorded sample."""
import json
from pathlib import Path

import pytest

from bench import trace

FIXTURE = json.loads((Path(__file__).parent / "data" /
                      "trace_events.json").read_text())["events"]


def test_recorded_sample_busy_idle_and_attribution():
    r = trace.reduce(FIXTURE)
    w = [e for e in FIXTURE if e[2] == trace.WINDOW_SPAN][0]
    assert r["window_s"] == pytest.approx(w[4] * 1e-9)
    assert r["chips"] == 1
    # busy by brute force over a 1 us grid of the window
    w0, w1 = w[3], w[3] + w[4]
    ops = [(max(s, w0), min(s + d, w1)) for p, _, _, s, d in FIXTURE
           if p.startswith("/device") and s + d > w0 and s < w1]
    grid = sum(any(a <= t < b for a, b in ops)
               for t in range(int(w0), int(w1), 1000)) * 1e-6
    assert r["busy_s"] == pytest.approx(grid, abs=2e-5)
    idle = dict(r["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # the 13 ms gap falls inside the engine tick the host was running
    assert max(idle, key=idle.get) == "bench.tick"
    assert idle["bench.tick"] > 0.012
    times = [t for _, t in r["device_ops"]]
    assert times == sorted(times, reverse=True) and len(times) <= 10
    assert sum(times) <= r["busy_s"] + 1e-9


def test_nested_loop_op_counts_once_and_top_ops_are_leaves():
    dev = "/device:TPU:0"
    ev = [("/host:CPU", "python3", trace.WINDOW_SPAN, 0.0, 100.0),
          ("/host:CPU", "python3", "bench.step", 0.0, 100.0),
          (dev, "XLA Ops", "%while.1 = (f32[2]) while()", 10.0, 50.0),
          (dev, "XLA Ops", "%fusion.2 = f32[4]{0} fusion()", 10.0, 20.0),
          (dev, "XLA Ops", "%fusion.3 = f32[8]{0} fusion()", 35.0, 20.0),
          (dev, "XLA Ops", "%copy.4 = f32[8]{0} copy()", 80.0, 40.0)]
    r = trace.reduce(ev)
    assert r["busy_s"] == pytest.approx(70e-9)      # [10,60) + [80,100)
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion.2 f32[4]": 20e-9, "fusion.3 f32[8]": 20e-9,
         "copy.4 f32[8]": 20e-9})
    assert dict(r["idle_gaps"]) == pytest.approx({"bench.step": 30e-9})


def test_no_window_or_no_device_reads_nothing():
    assert trace.reduce([]) is None
    assert trace.reduce([("/host:CPU", "python3", trace.WINDOW_SPAN,
                          0.0, 1.0)]) is None
