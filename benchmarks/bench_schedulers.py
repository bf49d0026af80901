"""Paper Figs 13-15: ICO vs RR / HUP / LQP — online response times
(avg/p90/p99) and cross-node CPU/MEM utilization std, identical traces.

The headline comparison is followed by the **batched axis** (always on):
each scheduler's placement plan from the headline trace is replayed over
>= 20 simulation seeds in one vmapped ``state.batched_rollout`` call, so
the ranking comes with error bars — p99 mean +/- std per scheduler and a
per-seed win/loss record against the HUP baseline — instead of a single
telemetry draw.

``--forecast`` additionally runs the **forecast axis**: ICO vs ICO-F on
day-scale bursty traces over >= 2 seeds, with a fresh ``ForecastService``
threaded through the ICO-F admission path.  The acceptance bars: ICO-F
mean p99 <= ICO mean p99 across the seeds, and an ICO-F replay *without*
a service bit-identical to ICO (exact fallback).  Day-scale traces are
mandatory — the forecaster's extrapolation-leverage gate only opens after
~0.9 of a diurnal period, so short traces would compare two identical
schedulers.

``--trace [PATH]`` (with ``--forecast``) records the first seed's ICO-F
run through a ``repro.obs.TraceRecorder`` and saves the JSONL admission
trace — every placement with its per-node Eq. (4)-(6) + forecast-term
breakdown, queryable via ``python -m repro.obs.explain PATH --pod UID``.

``--json PATH`` dumps the headline results plus the batched axis
(per-seed p99s, mean +/- std, win/loss vs HUP) as a machine-readable
artifact.
"""
from __future__ import annotations

import json
import sys
import time

from repro.cluster.experiment import (
    _arrival_trace,
    bursty_trace,
    compare_schedulers,
    make_schedulers,
    replay_plan_batched,
    run_experiment,
    train_default_predictor,
)

# day-scale bursty traces for the ICO-F axis: online fleet + recurring
# offline waves spread over >= 3 diurnal periods, so late-arriving burst
# jobs are admitted with the trust gate open (armed fraction ~0.7)
FORECAST_TRACE = dict(num_online=14, burst_gap=(140, 210), days=3.0)
FORECAST_SEEDS = [(0, 11), (1, 12)]
CONTROL_WINDOW = 40  # forecast-observation cadence inside day-scale gaps

# seed axis for the vmapped plan replay (>= 20 telemetry streams/plan)
BATCHED_SIM_SEEDS = tuple(range(20))


def _mean(xs):
    return sum(xs) / len(xs)


def _std(xs):
    m = _mean(xs)
    return (_mean([(x - m) ** 2 for x in xs])) ** 0.5


def run(fast: bool = True, forecast: bool = False,
        trace_path: str | None = None, json_path: str | None = None):
    n_pods = 40 if fast else 90
    t0 = time.time()
    res = compare_schedulers(num_pods=n_pods, num_nodes=12, seed=7)
    total_us = (time.time() - t0) * 1e6
    out = []
    json_doc: dict = {"fast": fast, "schedulers": {}}
    base = res["HUP"]
    for name, r in res.items():
        rel = (1 - r.avg_rt / base.avg_rt) * 100 if base.avg_rt else 0.0
        out.append((
            f"schedulers.{name}",
            total_us / len(res),
            f"avg_rt={r.avg_rt:.2f};p90={r.p90_rt:.2f};p99={r.p99_rt:.2f};"
            f"cpu_std={r.cpu_util_std:.2f};mem_std={r.mem_util_std:.2f};"
            f"placed={r.placed};vs_hup_avg={rel:+.1f}%",
        ))
        json_doc["schedulers"][name] = {
            "avg_rt": r.avg_rt, "p90_rt": r.p90_rt, "p99_rt": r.p99_rt,
            "cpu_util_std": r.cpu_util_std, "mem_util_std": r.mem_util_std,
            "placed": r.placed, "rejected": r.rejected,
        }
    _batched_axis(out, json_doc, n_pods=n_pods, fast=fast)
    if forecast:
        _forecast_axis(out, fast=fast, trace_path=trace_path)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(json_doc, f, indent=2)
    return out


def _batched_axis(out, json_doc, n_pods: int, fast: bool = True,
                  sim_seeds=BATCHED_SIM_SEEDS):
    """Replay every scheduler's plan over >= 20 vmapped sim seeds: ranking
    with error bars plus a per-seed win/loss record against HUP."""
    predictor = train_default_predictor(
        seed=7, num_placements=80 if fast else 250)
    pods, gaps = _arrival_trace(n_pods, seed=7)
    per_sched: dict[str, dict] = {}
    for name, sched in make_schedulers(predictor).items():
        plan: dict = {}
        run_experiment(sched, pods, gaps, num_nodes=12, seed=7,
                       plan_out=plan)
        batch = replay_plan_batched(plan, sim_seeds=sim_seeds)
        per_sched[name] = {
            "p99": [e["p99_rt"] for e in batch["seeds"]],
            "avg": [e["avg_rt"] for e in batch["seeds"]],
            "wall_s": batch["wall_s"],
        }
    hup = per_sched["HUP"]["p99"]
    json_doc["batched"] = {"sim_seeds": [int(s) for s in sim_seeds],
                           "schedulers": {}}
    for name, d in per_sched.items():
        wins = sum(p < h for p, h in zip(d["p99"], hup))
        out.append((
            f"schedulers.batched.{name}",
            d["wall_s"] * 1e6,
            f"seeds={len(sim_seeds)};"
            f"p99={_mean(d['p99']):.2f}+/-{_std(d['p99']):.2f};"
            f"avg={_mean(d['avg']):.2f}+/-{_std(d['avg']):.2f};"
            f"wins_vs_hup={wins}/{len(sim_seeds)}",
        ))
        json_doc["batched"]["schedulers"][name] = {
            "p99_mean": _mean(d["p99"]), "p99_std": _std(d["p99"]),
            "avg_mean": _mean(d["avg"]), "avg_std": _std(d["avg"]),
            "p99_per_seed": d["p99"],
            "wins_vs_hup": int(wins),
            "losses_vs_hup": int(len(sim_seeds) - wins),
            "wall_s": d["wall_s"],
        }


def _forecast_axis(out, fast: bool = True, trace_path: str | None = None):
    from repro.control import ForecastService

    predictor = train_default_predictor(
        seed=7, num_placements=80 if fast else 250)
    rows = []
    for i, (trace_seed, sim_seed) in enumerate(FORECAST_SEEDS):
        pods, gaps = bursty_trace(seed=trace_seed, **FORECAST_TRACE)
        scheds = make_schedulers(predictor, forecast=True)
        t0 = time.time()
        r_ico = run_experiment(scheds["ICO"], pods, gaps, num_nodes=12,
                               seed=sim_seed)
        svc = ForecastService()
        rec = None
        if trace_path and i == 0:
            from repro.obs import TraceRecorder
            rec = TraceRecorder()
        r_icof = run_experiment(scheds["ICO-F"], pods, gaps, num_nodes=12,
                                seed=sim_seed, forecast=svc,
                                control_window=CONTROL_WINDOW, recorder=rec)
        us = (time.time() - t0) * 1e6
        if rec is not None:
            n_events = rec.save(trace_path)
            out.append((
                "schedulers.forecast.trace",
                0.0,
                f"path={trace_path};events={n_events};"
                f"admissions={len(rec.query('admission'))}",
            ))
        row = {"ico": r_ico, "icof": r_icof}
        if i == 0:
            # exact-fallback bar: ICO-F without a service IS ICO
            r_fb = run_experiment(
                make_schedulers(predictor, forecast=True)["ICO-F"],
                pods, gaps, num_nodes=12, seed=sim_seed)
            row["fallback_exact"] = (r_fb.p99_rt == r_ico.p99_rt
                                     and r_fb.placed == r_ico.placed)
        rows.append(row)
        out.append((
            f"schedulers.forecast.seed{trace_seed}",
            us,
            f"p99_ico={r_ico.p99_rt:.2f};p99_icof={r_icof.p99_rt:.2f};"
            f"avg_ico={r_ico.avg_rt:.2f};avg_icof={r_icof.avg_rt:.2f};"
            f"win={r_icof.p99_rt <= r_ico.p99_rt}"
            + (f";fallback_exact={row['fallback_exact']}"
               if "fallback_exact" in row else ""),
        ))
    mean_ico = _mean([r["ico"].p99_rt for r in rows])
    mean_icof = _mean([r["icof"].p99_rt for r in rows])
    out.append((
        "schedulers.forecast.summary",
        0.0,
        f"mean_p99_ico={mean_ico:.2f};mean_p99_icof={mean_icof:.2f};"
        f"icof_beats_ico={mean_icof <= mean_ico}",
    ))


def _flag_value(argv, flag, default):
    if flag not in argv:
        return None
    i = argv.index(flag)
    if i + 1 < len(argv) and not argv[i + 1].startswith("--"):
        return argv[i + 1]
    return default


if __name__ == "__main__":
    from repro.launch.cache import enable_persistent_cache

    enable_persistent_cache()
    trace_path = _flag_value(sys.argv, "--trace",
                             "BENCH_schedulers_trace.jsonl")
    json_path = _flag_value(sys.argv, "--json", "BENCH_schedulers.json")
    for row in run(fast="--full" not in sys.argv,
                   forecast="--forecast" in sys.argv,
                   trace_path=trace_path, json_path=json_path):
        print(",".join(map(str, row)))
