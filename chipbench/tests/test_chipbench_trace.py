"""The reduction from profiler events to device numbers, on hand-made
events and on a small trace recorded on a TPU v5e."""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import pytest  # noqa: E402

from chipbench import trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tpu_trace_events.json")
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def test_union_merges_and_clips():
    covered, merged = tr.union_ns([(0, 10), (5, 20), (30, 40), (38, 60)],
                                  2, 50)
    assert covered == 18 + 20
    assert merged == [[2, 20], [30, 50]]


def test_reduce_hand_made_window():
    events = [
        ev(HOST, "python", "chipbench.window", 100, 1000),
        ev(HOST, "python", "chipbench.replay.call", 100, 500),
        ev(HOST, "python", "chipbench.admission", 700, 100),
        ev(DEV, "XLA Modules", "jit_step(12)", 50, 150),   # clipped: 100-200
        ev(DEV, "XLA Modules", "jit_init(3)", 150, 100),   # overlaps: 100-250
        ev(DEV, "XLA Modules", "jit_step(13)", 600, 100),  # 600-700
        ev(DEV, "XLA Ops", "fusion.1", 600, 100),          # not read
    ]
    r = tr.reduce_events(events)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(250e-9)
    assert r["modules"] == {"jit_step": pytest.approx(200e-9),
                            "jit_init": pytest.approx(100e-9)}
    assert r["top_ops"][0] == ("jit_step", pytest.approx(200e-9))
    # gaps 250-600 (midpoint inside the call) and 700-1100 (midpoint 900
    # lies outside every annotation but the window)
    assert r["idle_gaps"][0] == ["host outside the harness's calls",
                                 pytest.approx(400e-9)]
    assert r["idle_gaps"][1] == ["chipbench.replay.call",
                                 pytest.approx(350e-9)]


def test_no_window_or_no_device_gives_nothing():
    assert tr.reduce_events([ev(DEV, "XLA Modules", "f", 0, 1)]) is None
    assert tr.reduce_events([ev(HOST, "python", "chipbench.window", 0, 9)]) \
        is None


def test_recorded_tpu_trace():
    with open(DATA) as f:
        events = json.load(f)
    r = tr.reduce_events(events)
    window = next(e for e in events if e["name"] == "chipbench.window")
    lo, hi = window["start_ns"], window["start_ns"] + window["dur_ns"]
    ops = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
                 if e["line"] == "XLA Modules")
    busy = 0.0
    end = lo
    for s, t in ops:          # a second, plain sweep over the same events
        s, t = max(s, end), min(t, hi)
        if t > s:
            busy += t - s
            end = t
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert any(name.startswith("jit_step") for name in r["modules"])
    assert sum(s for _, s in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] \
        + 1e-12
