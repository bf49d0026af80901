"""The cell harness: finds every piece of a cell by name and runs it.

Nothing here names a configuration, a mix or a metric.  A cell
``<name>`` is ``workloads/<name>.json``; it names its configuration
(``configs/<config>.json``) and its driver kind (``drivers/<kind>.py``);
each per-layer metric is ``metrics/<metric>.py``.  ``BENCHMARK.json`` at
the checkout's root says which metrics a cell reports; a cell it does not
list (a rehearsal, a test) reports whatever its driver and readers find.

A driver module provides::

    setup(cell) -> ctx            # inputs from the seed, warm-up
    call(ctx, i) -> record        # one timed unit of work
    end_to_end(ctx, records) -> {metric: (value, unit)}
    attempted(records) -> (attempted, failed)
    release(ctx)                  # drop the program's device state
    check(ctx, records, dtype=...) -> {number: value}

and may provide ``annotate(ctx)`` (wrap program calls in host spans for
a traced run).  A metric reader provides ``UNIT`` and ``read(run)``,
returning a number or None when the run holds nothing to read.

A workload file names ``config``, ``driver``, ``traffic``, ``chips``,
``why``, the ``limits`` of the numbers its driver compares, optionally
``trace_calls`` (a traced window ends after that many calls), and the
driver's own parameters.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _log(msg: str) -> None:
    sys.stderr.write(f"chipbench: {msg}\n")
    sys.stderr.flush()


def _load_json(kind: str, name: str, dirs) -> dict:
    for d in dirs:
        path = os.path.join(d, f"{name}.json")
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
    raise FileNotFoundError(f"no {kind} named {name!r} in {list(dirs)}")


def load_module(kind: str, name: str, dirs=None):
    """``<dir>/<name>.py`` as a module (names may hold dots)."""
    for d in dirs or [os.path.join(HERE, kind)]:
        path = os.path.join(d, f"{name}.py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"chipbench_{kind}_{name.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no {kind} module named {name!r}")


@dataclasses.dataclass
class Cell:
    """Everything one run of one cell needs, resolved by name."""

    name: str
    workload: dict
    config: dict
    seed: int
    chips: int

    def seeds(self, *path: int, count: int = 1) -> list[int]:
        """Seeds below 2**31 drawn from (--seed, *path): every random
        choice of a run descends from ``--seed`` through one of these."""
        ss = np.random.SeedSequence([self.seed % 2**63, *path])
        return [int(v) >> 1 for v in ss.generate_state(count)]


def load_cell(name: str, seed: int, workload_dirs=(), config_dirs=()) -> Cell:
    wl = _load_json("workload", name,
                    [*workload_dirs, os.path.join(HERE, "workloads")])
    cfg = _load_json("configuration", wl["config"],
                     [*config_dirs, os.path.join(HERE, "configs")])
    return Cell(name=name, workload=wl, config=cfg, seed=seed,
                chips=int(wl["chips"]))


def metric_lists(cell: str) -> tuple[dict, dict] | None:
    """(end_to_end, per_layer) entries of BENCHMARK.json that this cell
    reports, by name; None when the file does not list the cell."""
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        bench = json.load(f)
    if cell not in {w["name"] for w in bench["workloads"]}:
        return None

    def pick(entries):
        return {m["name"]: m for m in entries
                if cell in m.get("workloads", [cell])}

    return pick(bench["end_to_end"]), pick(bench["per_layer"])


@dataclasses.dataclass
class Run:
    """What per-layer readers see of a finished window."""

    cell: Cell
    records: list
    trace: dict | None


def rel_gap(got: float, want: float) -> float:
    """Relative gap of an answer to the reference's (inf if either is
    not finite)."""
    if not (math.isfinite(got) and math.isfinite(want)):
        return float("inf")
    return abs(got - want) / max(abs(want), 1e-12)


def require_chips(chips: int):
    """The accelerator devices, or exit non-zero: a measurement never
    falls back to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        sys.stderr.write(f"chipbench: no accelerator; JAX found "
                         f"{len(devices)} cpu device(s)\n")
        sys.exit(2)
    if len(devices) < chips:
        sys.stderr.write(f"chipbench: the cell needs {chips} chips, JAX "
                         f"found {len(devices)}\n")
        sys.exit(2)
    return devices[:chips]


def device_info(devices, trace: dict | None) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}
    if trace is not None:
        info["busy_s"] = trace["busy_s"]
        info["window_s"] = trace["window_s"]
    return info


def run_cell(cell: Cell, seconds: float, trace: bool, devices,
             start: float, trace_dir: str | None = None) -> dict:
    """Set up, measure for ``seconds``, check; returns the result dict
    (the caller prints it)."""
    import jax

    driver = load_module("drivers", cell.workload["driver"])
    ctx = driver.setup(cell)
    setup_s = time.perf_counter() - start

    if trace:
        trace_dir = trace_dir or os.path.join(HERE, ".trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if hasattr(driver, "annotate"):
            driver.annotate(ctx)
        # no Python-call tracer and no HLO protos: they multiply the size
        # of the trace and slow the traced calls; the harness's own
        # annotations are host events of the first level
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    records = []
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if "backend_compile" in event else None)
    # a traced run may stop after the cell's ``trace_calls`` calls: a
    # device trace holds every operation of every scan step
    cap = cell.workload.get("trace_calls") if trace else None
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.window"):
        while (time.perf_counter() - t0 < seconds
               and (cap is None or len(records) < cap)):
            with jax.profiler.TraceAnnotation(
                    f"chipbench.{cell.workload['driver']}.call"):
                records.append(driver.call(ctx, len(records)))
    window_s = time.perf_counter() - t0
    in_window = len(compiles)
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        from chipbench.trace_reduce import read_xplane, reduce_events

        reduced = reduce_events(read_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    device = device_info(devices, reduced)
    _log(f"setup {setup_s:.3f} s, window {window_s:.3f} s with "
         f"{len(records)} calls and {in_window} compilations, "
         f"{time.perf_counter() - t0 - window_s:.3f} s to read the trace")

    wanted = metric_lists(cell.name)
    if trace:
        run = Run(cell, records, reduced)
        names = (sorted(wanted[1]) if wanted is not None else sorted(
            f[:-3] for f in os.listdir(os.path.join(HERE, "metrics"))
            if f.endswith(".py")))
        metrics = {}
        for name in names:
            reader = load_module("metrics", name)
            value = reader.read(run)
            if value is not None:
                metrics[name] = {"value": value, "unit": reader.UNIT}
    else:
        found = dict(driver.end_to_end(ctx, records))
        found["setup_s"] = (setup_s, "s")
        metrics = {k: {"value": v, "unit": u} for k, (v, u) in found.items()
                   if wanted is None or k in wanted[0]}
    attempted, failed = driver.attempted(records)

    driver.release(ctx)
    t1 = time.perf_counter()
    numbers = driver.check(ctx, records)
    _log(f"reference check {time.perf_counter() - t1:.3f} s")
    limits = cell.workload["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    for k, c in checks.items():
        sys.stderr.write(f"check {k}: {c['value']!r} limit {c['limit']!r}\n")
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if reduced is not None:
        out["breakdown"] = {"device_ops": reduced["top_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    return out
