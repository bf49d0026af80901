"""The program's spans in a profiler trace: the span table, idle gaps
named by the innermost span, and the harness's numbers left as they
were, on hand-made events, on a CPU profile of a tiny replay and on a
small trace recorded on a TPU v5e."""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import pytest  # noqa: E402

from chipbench import spans  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402
from chipbench.tests import tinycells  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV = "/device:TPU:0"
HOST = "/host:CPU"
REPLAY_SPANS = {f"repro.replay.{p}" for p in
                ("call", "inputs", "plan", "keys", "engine", "reduce")}
HARNESS_NUMBERS = ("window_s", "busy_s", "modules", "top_ops")


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def without_program_spans(events):
    return [e for e in events if not e["name"].startswith(spans.SPAN_PREFIX)]


# one replay call in a 1000-ns window: three key-stream programs, the
# engine, and a program at the window's end; a loop span crosses the end
HAND_MADE = [
    ev(HOST, "python", "chipbench.window", 0, 1000),
    ev(HOST, "python", "chipbench.replay.call", 0, 900),
    ev(HOST, "python", "repro.replay.call", 10, 880),
    ev(HOST, "python", "repro.replay.inputs", 10, 490),
    ev(HOST, "python", "repro.replay.plan", 10, 40),
    ev(HOST, "python", "repro.replay.keys", 50, 440),
    ev(HOST, "python", "repro.replay.engine", 500, 300),
    ev(HOST, "python", "repro.replay.reduce", 800, 90),
    ev(HOST, "python", "repro.loop.rollout", 950, 250),    # clipped: 950-1000
    ev(HOST, "python", "repro.loop.detect", 1100, 50),     # outside
    ev(DEV, "XLA Modules", "jit__threefry_split(1)", 100, 10),
    ev(DEV, "XLA Modules", "jit__unstack(2)", 120, 5),
    ev(DEV, "XLA Modules", "jit__threefry_split(1)", 200, 10),
    ev(DEV, "XLA Modules", "jit__scan_windows_impl(3)", 520, 260),
    ev(DEV, "XLA Modules", "jit_x(4)", 950, 150),          # clipped: 950-1000
    ev(DEV, "XLA Ops", "fusion.1", 520, 100),              # not read
]


def test_span_table_hand_made():
    t = spans.span_table(HAND_MADE)
    want = {   # total, self, idle, launches (ns, ns, ns, count)
        "repro.replay.call": (880, 0, 880 - 285, 4),
        "repro.replay.inputs": (490, 10, 490 - 25, 3),
        "repro.replay.plan": (40, 40, 40, 0),
        "repro.replay.keys": (440, 440, 440 - 25, 3),
        "repro.replay.engine": (300, 300, 40, 1),
        "repro.replay.reduce": (90, 90, 90, 0),
        "repro.loop.rollout": (50, 50, 0, 1),
    }
    assert set(t) == set(want)
    for name, (total, self_ns, idle, launches) in want.items():
        row = t[name]
        assert row["count"] == 1, name
        assert row["total_s"] == pytest.approx(total * 1e-9), name
        assert row["self_s"] == pytest.approx(self_ns * 1e-9, abs=1e-15), name
        assert row["idle_s"] == pytest.approx(idle * 1e-9, abs=1e-15), name
        assert row["launches"] == launches, name


def test_idle_gaps_named_by_innermost_program_span():
    r = spans.reduce(HAND_MADE)
    # busy: 100-110, 120-125, 200-210, 520-780, 950-1000
    assert r["idle_gaps"][:3] == [
        ["repro.replay.keys", pytest.approx(310e-9)],     # 210-520
        ["repro.replay.reduce", pytest.approx(170e-9)],   # 780-950
        ["repro.replay.keys", pytest.approx(100e-9)],     # 0-100
    ]
    # without the program's spans only the harness's annotation names them
    old = tr.reduce_events(without_program_spans(HAND_MADE))
    assert old["idle_gaps"][0] == ["chipbench.replay.call",
                                   pytest.approx(310e-9)]
    for k in HARNESS_NUMBERS:
        assert r[k] == old[k], k


def test_idle_under_a_span_averages_the_devices():
    events = [ev(HOST, "t", "chipbench.window", 0, 100),
              ev(HOST, "t", "repro.replay.engine", 0, 100),
              ev(DEV, "XLA Modules", "jit_a(1)", 0, 60),
              ev("/device:TPU:1", "XLA Modules", "jit_a(1)", 0, 20)]
    row = spans.span_table(events)["repro.replay.engine"]
    assert row["idle_s"] == pytest.approx(60e-9)   # 40 idle and 80 idle
    assert row["launches"] == 2
    assert spans.span_table(events[1:]) is None    # no window


def test_cpu_profile_holds_nested_replay_spans(tmp_path):
    """A real profile of a tiny replay call on the CPU: the program's six
    spans, once each, nested inside the harness's call annotation."""
    tinycells.write_cell(str(tmp_path), tinycells.limits_of("paper12.replay"))
    cell = tinycells.load(str(tmp_path), "tiny.replay")
    _, traced, events = spans.record(cell, calls=1, untraced=0,
                                     trace_dir=str(tmp_path / "trace"))
    assert len(traced) == 1
    t = spans.span_table(events)
    assert set(t) == REPLAY_SPANS
    assert all(row["count"] == 1 for row in t.values())
    call = next(e for e in events if e["name"] == "chipbench.replay.call")
    for e in events:
        if e["name"] in REPLAY_SPANS:
            assert call["start_ns"] <= e["start_ns"]
            assert e["start_ns"] + e["dur_ns"] <= (call["start_ns"]
                                                    + call["dur_ns"])
    children = sum(t[f"repro.replay.{p}"]["total_s"]
                   for p in ("inputs", "engine", "reduce"))
    assert children <= t["repro.replay.call"]["total_s"]
    assert t["repro.replay.call"]["self_s"] == pytest.approx(
        t["repro.replay.call"]["total_s"] - children)


def test_recorded_tpu_trace_with_spans():
    """One call of ``tests/data/tiny-spans.replay.json`` recorded on a TPU
    v5e (``spans.py --workload tiny-spans.replay --workload-dir
    chipbench/tests/data --calls 1 --untraced 0 --events-out ...``)."""
    with open(os.path.join(DATA, "tpu_trace_spans.json")) as f:
        events = json.load(f)
    r = spans.reduce(events)
    old = tr.reduce_events(without_program_spans(events))
    for k in HARNESS_NUMBERS:
        assert r[k] == old[k], k
    t = r["spans"]
    assert set(t) == REPLAY_SPANS
    assert all(row["count"] == 1 for row in t.values())
    assert all(name.startswith("repro.replay.") for name, _ in r["idle_gaps"])

    call = t["repro.replay.call"]
    parts = sum(t[f"repro.replay.{p}"]["total_s"]
                for p in ("inputs", "engine", "reduce"))
    assert 0.95 * call["total_s"] <= parts <= call["total_s"]
    # every key split launches inside the key stream's span
    keys = next(e for e in events if e["name"] == "repro.replay.keys")
    splits = [e["start_ns"] for e in events
              if e["name"].startswith("jit__threefry_split")]
    assert splits and all(keys["start_ns"] <= s < keys["start_ns"]
                          + keys["dur_ns"] for s in splits)
    assert t["repro.replay.keys"]["launches"] >= 3 * len(splits)

    # idle under the call, by a second, plain sweep over the same events
    span = next(e for e in events if e["name"] == "repro.replay.call")
    lo, hi = span["start_ns"], span["start_ns"] + span["dur_ns"]
    ran, end = 0.0, lo
    for s, e in sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                       for e in events if e["line"] == tr.MODULES_LINE):
        s, e = max(s, end), min(e, hi)
        if e > s:
            ran += e - s
            end = e
    assert call["idle_s"] == pytest.approx((hi - lo - ran) * 1e-9)
    assert call["launches"] == sum(
        lo <= e["start_ns"] < hi for e in events
        if e["line"] == tr.MODULES_LINE)
