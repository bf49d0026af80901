"""Reduction of a profiler trace to the benchmark's device numbers.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain event records; ``reduce_events`` turns those into:

* ``window_s`` — the harness's own ``chipbench.window`` annotation;
* ``busy_s`` — per device, the union of the intervals in which a compiled
  program ran (the device planes' ``XLA Modules`` line), clipped to the
  window, averaged over the devices that ran anything;
* ``modules`` — device seconds per compiled program, keyed by the
  program's name without its ``(id)`` suffix;
* ``top_ops`` — the programs that took most device time;
* ``idle_gaps`` — the longest gaps between programs, each named by the
  innermost ``chipbench.*`` host annotation around its midpoint.

The device planes also hold a line of single operations (``XLA Ops``):
one event per operation per scan step, tens of millions in a replay
call, which Python cannot read within a run.  The reduction reads the
programs only; the time a scan spends between its own steps counts as
busy.

Keeping this in one place means every later change computes the same
numbers the same way.  ``tests/data/tpu_trace_events.json`` is a small
trace recorded on a TPU v5e, reduced by the tests.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "chipbench.window"
ANNOTATION_PREFIX = "chipbench."
MODULES_LINE = "XLA Modules"


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def read_xplane(trace_dir: str) -> list[dict]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir`` that
    the reduction reads: device programs and the harness's host
    annotations.  Records are ``{"plane", "line", "name", "start_ns",
    "dur_ns"}``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            if device and line.name != MODULES_LINE:
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(ANNOTATION_PREFIX):
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": float(ev.start_ns),
                            "dur_ns": float(ev.duration_ns)})
    return out


def union_ns(intervals, lo: float, hi: float) -> tuple[float, list]:
    """Covered length of ``intervals`` inside [lo, hi] and the merged
    intervals themselves."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _base_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def reduce_events(events: list[dict], top: int = 10) -> dict | None:
    """The device numbers of one traced window, or None when the trace
    holds no window annotation or no device operation."""
    windows = [e for e in events if e["name"] == WINDOW
               and not is_device_plane(e["plane"])]
    if not windows:
        return None
    w = max(windows, key=lambda e: e["dur_ns"])
    lo, hi = w["start_ns"], w["start_ns"] + w["dur_ns"]

    by_device: dict[str, list] = {}
    modules: dict[str, float] = {}
    for e in events:
        if not is_device_plane(e["plane"]) or e["line"] != MODULES_LINE:
            continue
        start, end = e["start_ns"], e["start_ns"] + e["dur_ns"]
        by_device.setdefault(e["plane"], []).append((start, end))
        s, t = max(start, lo), min(end, hi)
        if t > s:
            key = _base_name(e["name"])
            modules[key] = modules.get(key, 0.0) + (t - s) * 1e-9
    busy, gaps = [], []
    for plane in sorted(by_device):
        covered, merged = union_ns(by_device[plane], lo, hi)
        if covered <= 0:
            continue
        busy.append(covered * 1e-9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    if not busy:
        return None

    notes = [e for e in events if not is_device_plane(e["plane"])
             and e["name"] != WINDOW]

    def what(mid: float) -> str:
        inside = [e for e in notes
                  if e["start_ns"] <= mid < e["start_ns"] + e["dur_ns"]]
        if not inside:
            return "host outside the harness's calls"
        return min(inside, key=lambda e: e["dur_ns"])["name"]

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy),
        "modules": modules,
        "top_ops": sorted(modules.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[what((a + b) / 2), (b - a) * 1e-9]
                      for a, b in gaps[:top]],
    }
