"""The program's own spans in a profiler trace, and a traced run that
splits a cell's calls by them.

The program opens its spans through ``repro.obs.PhaseTimers`` as
``jax.profiler.TraceAnnotation`` host events, on the device trace's
clock: ``repro.replay.*`` in a replay call (``call``; ``inputs`` with
``plan`` and ``keys`` inside it; ``engine``; ``reduce``) and
``repro.loop.*`` in ``run_experiment`` (``rollout``, ``snapshot``,
``verify``, ``forecast``, ``detect``, ``plan``).

``trace_reduce.read_xplane`` keeps the harness's own annotations only;
``read_events`` keeps the program's spans beside them, so that
``trace_reduce.reduce_events`` over its events names each idle gap by the
innermost span of either prefix, and leaves ``window_s``, ``busy_s``,
``modules`` and ``top_ops`` as they were (they read no host event but the
window).  ``span_table`` gives, for each span name, clipped to the
window:

* ``total_s`` and ``count``;
* ``self_s`` — the span's time that no program span inside it covers;
* ``idle_s`` — the span's time in which no compiled program ran on the
  device, averaged over the devices that ran anything, as ``busy_s`` is;
* ``launches`` — the ``XLA Modules`` events that start inside it.

Run as a script on the chip, it sets up a cell, times ``--untraced``
calls with the profiler off, then ``--calls`` calls under the profiler
(the harness's options and annotations), and prints one JSON object: the
call times of both sets and ``reduce`` of the trace::

    python3 chipbench/spans.py --workload paper12.replay --seed <n> \\
        [--calls 2] [--untraced 1] [--workload-dir DIR] [--events-out PATH]

``--events-out`` writes the events it read, as ``tests/data`` holds them.
"""
from __future__ import annotations

import bisect
import glob
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import trace_reduce as tr  # noqa: E402

SPAN_PREFIX = "repro."


def read_events(trace_dir: str) -> list[dict]:
    """``trace_reduce.read_xplane``'s events of the newest ``.xplane.pb``
    under ``trace_dir``, with the program's spans kept as well."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    keep = (tr.ANNOTATION_PREFIX, SPAN_PREFIX)
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = tr.is_device_plane(plane.name)
        for line in plane.lines:
            if device and line.name != tr.MODULES_LINE:
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(keep):
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": float(ev.start_ns),
                            "dur_ns": float(ev.duration_ns)})
    return out


def _window(events: list[dict]) -> tuple[float, float] | None:
    windows = [e for e in events if e["name"] == tr.WINDOW
               and not tr.is_device_plane(e["plane"])]
    if not windows:
        return None
    w = max(windows, key=lambda e: e["dur_ns"])
    return w["start_ns"], w["start_ns"] + w["dur_ns"]


def _covered(merged: list, starts: list, a: float, b: float) -> float:
    """Length of [a, b] that the sorted, disjoint ``merged`` intervals
    cover (``starts`` their start points)."""
    total = 0.0
    for s, e in merged[max(bisect.bisect_right(starts, a) - 1, 0):]:
        if s >= b:
            break
        total += max(0.0, min(e, b) - max(s, a))
    return total


def span_table(events: list[dict]) -> dict[str, dict] | None:
    """Per program span name: ``total_s``, ``count``, ``self_s``,
    ``idle_s`` and ``launches`` inside the harness's window; None when
    the trace holds no window."""
    window = _window(events)
    if window is None:
        return None
    lo, hi = window
    by_device: dict[str, list] = {}
    for e in events:
        if tr.is_device_plane(e["plane"]) and e["line"] == tr.MODULES_LINE:
            by_device.setdefault(e["plane"], []).append(
                (e["start_ns"], e["start_ns"] + e["dur_ns"]))
    busy = []
    for plane in sorted(by_device):
        covered, merged = tr.union_ns(by_device[plane], lo, hi)
        if covered > 0:
            busy.append((merged, [s for s, _ in merged]))
    launches = sorted(s for ivs in by_device.values() for s, _ in ivs)

    spans = sorted(
        ((max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi),
          e["plane"], e["line"], e["name"]) for e in events
         if e["name"].startswith(SPAN_PREFIX)
         and not tr.is_device_plane(e["plane"])),
        key=lambda s: (s[0], -s[1]))
    spans = [s for s in spans if s[1] > s[0]]
    table: dict[str, dict] = {}
    for i, (a, b, plane, line, name) in enumerate(spans):
        inner = []
        for s, e, p, ln, _ in spans[i + 1:]:   # sorted by start
            if s >= b:
                break
            if e <= b and (p, ln) == (plane, line):
                inner.append((s, e))
        children, _ = tr.union_ns(inner, a, b)
        ran = (sum(_covered(m, st, a, b) for m, st in busy) / len(busy)
               if busy else 0.0)
        row = table.setdefault(name, {"total_s": 0.0, "count": 0,
                                      "self_s": 0.0, "idle_s": 0.0,
                                      "launches": 0})
        row["total_s"] += (b - a) * 1e-9
        row["count"] += 1
        row["self_s"] += (b - a - children) * 1e-9
        row["idle_s"] += (b - a - ran) * 1e-9
        row["launches"] += (bisect.bisect_left(launches, b)
                            - bisect.bisect_left(launches, a))
    return table


def reduce(events: list[dict]) -> dict | None:
    """``trace_reduce.reduce_events`` of the events plus their
    ``spans`` (``span_table``)."""
    out = tr.reduce_events(events)
    if out is not None:
        out["spans"] = span_table(events)
    return out


def record(cell, calls: int, untraced: int, trace_dir: str):
    """Set up ``cell``, run ``untraced`` calls, then ``calls`` calls under
    the profiler in the harness's window and call annotations.  Returns
    (untraced records, traced records, the trace's events)."""
    import jax

    from chipbench import harness

    kind = cell.workload["driver"]
    driver = harness.load_module("drivers", kind)
    ctx = driver.setup(cell)
    plain = [driver.call(ctx, i) for i in range(untraced)]
    # the harness's profiler options: no Python-call tracer, no HLO protos
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    traced = []
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        for i in range(calls):
            with jax.profiler.TraceAnnotation(f"chipbench.{kind}.call"):
                traced.append(driver.call(ctx, untraced + i))
    jax.profiler.stop_trace()
    driver.release(ctx)
    return plain, traced, read_events(trace_dir)


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import time

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, default=2)
    ap.add_argument("--untraced", type=int, default=1)
    ap.add_argument("--workload-dir", action="append", default=[],
                    help="a directory searched for the workload first")
    ap.add_argument("--events-out")
    args = ap.parse_args(argv)

    sys.path.insert(1, os.path.join(ROOT, "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    from chipbench import harness
    from repro.launch.cache import enable_persistent_cache

    cell = harness.load_cell(args.workload, args.seed,
                             workload_dirs=args.workload_dir)
    harness.require_chips(cell.chips)
    enable_persistent_cache()
    trace_dir = os.path.join(ROOT, "chipbench", ".trace", f"spans-{cell.name}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    t0 = time.perf_counter()
    plain, traced, events = record(cell, args.calls, args.untraced,
                                   trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    if args.events_out:
        with open(args.events_out, "w") as f:
            json.dump(events, f, indent=0)

    def walls(records):
        return [r["end"] - r["start"] for r in records]

    sys.stdout.write(json.dumps({
        "cell": cell.name, "seed": args.seed,
        "seconds": time.perf_counter() - t0,
        "untraced_call_s": walls(plain), "traced_call_s": walls(traced),
        "reduced": reduce(events)}) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
