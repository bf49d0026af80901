"""Runtime mitigation benchmark: per-scheduler profiles and the proactive
forecast channel, on bursty offline load, across several trace seeds.

Two grids:

* **Profile grid** (always) — every scheduler (ICO / RR / HUP / LQP) with
  and without a fresh ControlLoop built from its *tuned* per-scheduler
  profile (``scheduler_loop_config``), on the PR-2 short bursty traces.
  The acceptance bars here: ICO+control keeps beating plain ICO, and the
  conservative RR/HUP profiles make mitigation non-harmful on the seeds
  where the one-size-fits-all config regressed.

* **Proactive axis** (``--proactive``) — ICO replayed three ways on
  multi-day (>= 3 diurnal periods) bursty traces: no mitigation, reactive
  mitigation, and proactive mitigation (forecast channel on).  The
  seasonal forecaster needs to observe ≈ a full diurnal period before its
  extrapolation-leverage gate opens, so on the old ~1.7-day traces the
  channel was only armed for ~0.7 of a period and its steady-state value
  was unmeasurable; at 3 days the armed fraction is ~0.7 of the whole
  trace.  Inter-arrival gaps are sliced into ``control_window``-tick
  windows so the loop acts on a uniform cadence inside the long gaps.
  A fourth mode, **unified**, runs the full ClusterView/ForecastService
  stack: ICO-F admission and proactive mitigation sharing ONE projection
  service, so placement and runtime correction agree about where load is
  heading.  Reported per seed: the p99 of each mode, proactive flag/action
  counts, and the forecaster's one-step calibration error.

* **Batched axis** (always) — the acceptance demo for the vmapped rollout
  core: one 3-day ICO trace is run under 2 sim seeds by ``run_experiment``,
  whose Python loop advances ``Cluster.rollout`` window by window (the
  ``python_loop`` row), then its placement/action plans, one without
  mitigation and one with a reactive ControlLoop, are replayed over >= 20
  sim seeds in ONE ``state.batched_rollout`` call each.  Reported: both wall-clocks,
  per-seed cost of each path, p99 mean +/- std per mode across seeds, the
  per-seed mitigation win/loss record, and a parity check (the replay
  entry whose seed equals the reference run's must reproduce its p99).

Cost-model calibration (total predicted vs realized reduction, per-kind
corrections) is carried exactly as before.

``--json PATH`` additionally dumps the full grid as a machine-readable
artifact (CI uploads it as BENCH_control.json so the perf trajectory of
the control plane — including the reactive-vs-proactive p99 delta — is
tracked per commit).

``--trace [PATH]`` (with ``--proactive``) records the first seed's
*unified* run through a ``repro.obs.TraceRecorder`` and saves the JSONL
decision trace next to the JSON artifact; the bench then verifies the
Planned -> Executed -> Verified/Discarded chain of every executed action
straight from the trace (the ISSUE-6 acceptance bar) and reports the
result in both the row output and the JSON document.  Query the artifact
with ``python -m repro.obs.explain PATH``.
"""
from __future__ import annotations

import json
import sys
import time

from repro.cluster.experiment import (
    bursty_trace,
    make_schedulers,
    replay_plan_batched,
    run_experiment,
    train_default_predictor,
)
from repro.control import ControlLoop, ForecastService, scheduler_loop_config
from repro.core import InterferenceQuantifier
from repro.obs import Trace, TraceRecorder
from repro.obs.explain import action_chains

SCHEDULERS = ("ICO", "RR", "HUP", "LQP")

# the proactive axis needs multi-day traces: the forecaster's leverage gate
# only trusts extrapolation once ~a full diurnal period has been observed,
# so >= 3 days keeps the channel armed for most of the run instead of its
# last stretch (the `days` knob sizes num_bursts to cover the span)
PROACTIVE_TRACE = dict(num_online=14, burst_gap=(140, 210), days=3.0)
CONTROL_WINDOW = 40

# default seed axis for the vmapped plan replay — the acceptance bar wants
# >= 20 independent telemetry streams per plan, in one batched_rollout call
BATCHED_SIM_SEEDS = tuple(range(20))


def _mean(xs):
    return sum(xs) / len(xs)


def _profile_grid(predictor, seeds, out, json_doc):
    grid: dict[str, dict[str, list]] = {
        name: {"off": [], "on": []} for name in SCHEDULERS
    }
    corrections: dict[str, list[float]] = {}
    calib = {"predicted": 0.0, "realized": 0.0, "mitigations": 0,
             "mean_abs_errors": []}
    times_us: dict[str, list[float]] = {}

    for trace_seed, sim_seed in seeds:
        pods, gaps = bursty_trace(num_online=14, seed=trace_seed)
        for with_control in (False, True):
            # fresh scheduler instances per run: RR's rotation pointer (and
            # any other scheduler state) must not leak between the with-
            # and without-mitigation replays of the same trace
            for name, sched in make_schedulers(predictor).items():
                loop = None
                if with_control:
                    loop = ControlLoop(
                        InterferenceQuantifier(predictor.predict),
                        scheduler_loop_config(name),
                    )
                t0 = time.time()
                r = run_experiment(sched, pods, gaps, num_nodes=12,
                                   seed=sim_seed, control_loop=loop)
                times_us.setdefault(name, []).append((time.time() - t0) * 1e6)
                grid[name]["on" if with_control else "off"].append(r)
                if loop is not None:
                    calib["predicted"] += r.predicted_reduction
                    calib["realized"] += r.realized_reduction
                    calib["mitigations"] += r.mitigations
                    # the canonical per-verified-action denominator lives on
                    # ControlStats now — no more ad-hoc re-derivation here
                    s = loop.stats
                    if s.actions_verified:
                        calib["mean_abs_errors"].append(
                            s.mean_calibration_abs_error)
                    for kind, corr in loop.corrections.items():
                        corrections.setdefault(kind, []).append(corr)

    for name in SCHEDULERS:
        p99_off = _mean([r.p99_rt for r in grid[name]["off"]])
        p99_on = _mean([r.p99_rt for r in grid[name]["on"]])
        avg_off = _mean([r.avg_rt for r in grid[name]["off"]])
        avg_on = _mean([r.avg_rt for r in grid[name]["on"]])
        mits = sum(r.mitigations for r in grid[name]["on"])
        gain = (1 - p99_on / p99_off) * 100
        out.append((
            f"control.grid.{name}",
            _mean(times_us[name]),  # mean across all seeds x modes in the row
            f"p99_off={p99_off:.2f};p99_on={p99_on:.2f};"
            f"avg_off={avg_off:.2f};avg_on={avg_on:.2f};"
            f"mitigations={mits};p99_gain={gain:+.1f}%",
        ))

    # acceptance bars, per seed: ICO+control beats plain ICO; the tuned
    # RR/HUP profiles keep mitigation non-harmful (p99 delta <= 0-ish)
    for i, (trace_seed, sim_seed) in enumerate(seeds):
        off, on = grid["ICO"]["off"][i], grid["ICO"]["on"][i]
        out.append((
            f"control.ICO.seed{trace_seed}",
            0.0,
            f"p99_off={off.p99_rt:.2f};p99_on={on.p99_rt:.2f};"
            f"win={on.p99_rt < off.p99_rt}",
        ))
    for name in ("RR", "HUP"):
        for i, (trace_seed, _) in enumerate(seeds):
            off, on = grid[name]["off"][i], grid[name]["on"][i]
            out.append((
                f"control.profile.{name}.seed{trace_seed}",
                0.0,
                f"p99_off={off.p99_rt:.2f};p99_on={on.p99_rt:.2f};"
                f"non_harmful={on.p99_rt <= off.p99_rt}",
            ))

    rel_err = (abs(calib["realized"] - calib["predicted"])
               / max(calib["predicted"], 1e-9))
    mean_abs = (_mean(calib["mean_abs_errors"])
                if calib["mean_abs_errors"] else float("nan"))
    corr_str = ";".join(
        f"corr_{k}={_mean(v):.2f}" for k, v in sorted(corrections.items()))
    out.append((
        "control.calibration",
        0.0,
        f"predicted={calib['predicted']:.1f};realized={calib['realized']:.1f};"
        f"rel_err={rel_err:.2f};mean_abs_error={mean_abs:.1f};"
        f"mitigations={calib['mitigations']};{corr_str}",
    ))

    json_doc["grid"] = {
        name: {
            mode: [
                {"p99_rt": r.p99_rt, "avg_rt": r.avg_rt,
                 "p90_rt": r.p90_rt, "placed": r.placed,
                 "rejected": r.rejected, "mitigations": r.mitigations,
                 "proactive_mitigations": r.proactive_mitigations,
                 "predicted_reduction": r.predicted_reduction,
                 "realized_reduction": r.realized_reduction}
                for r in runs
            ]
            for mode, runs in modes.items()
        }
        for name, modes in grid.items()
    }
    json_doc["calibration"] = {
        "predicted": calib["predicted"],
        "realized": calib["realized"],
        "rel_err": rel_err,
        "mean_abs_error_per_action": (mean_abs if mean_abs == mean_abs
                                      else None),
        "corrections": {k: _mean(v) for k, v in corrections.items()},
    }


def _batched_axis(out, json_doc, sim_seeds=BATCHED_SIM_SEEDS):
    """Acceptance axis: >= 20 vmapped seeds vs 2 per-window Python-driven
    runs, on one 3-day ICO trace, with and without reactive mitigation.

    Every run here uses the same lightweight linear predictor (not the
    trained RF), so the rows time the rollout core, not scheduler quality.
    The baseline is the 2-seed protocol of ``run_experiment``, one
    ``Cluster.rollout`` dispatch per window driven from Python.
    """
    from repro.core import ICOScheduler

    import numpy as np

    pods, gaps = bursty_trace(seed=0, **PROACTIVE_TRACE)
    ref_seed = 11
    quantify = InterferenceQuantifier(lambda x: np.asarray(x)[:, 0] * 0.1)

    # the 2-seed protocol, driven from Python window by window
    plan_off: dict = {}
    baseline = []
    t0 = time.time()
    for i, sim_seed in enumerate((ref_seed, ref_seed + 1)):
        sched = ICOScheduler(quantify)
        baseline.append(run_experiment(
            sched, pods, gaps, num_nodes=12, seed=sim_seed,
            control_window=CONTROL_WINDOW,
            plan_out=plan_off if i == 0 else None))
    python_wall = time.time() - t0

    # reactive reference run; its plan carries the
    # control loop's migrations/resizes, so the replay exercises the full
    # event vocabulary
    sched = ICOScheduler(quantify)
    loop = ControlLoop(quantify, scheduler_loop_config("ICO"))
    plan_on: dict = {}
    run_experiment(sched, pods, gaps, num_nodes=12, seed=ref_seed,
                   control_loop=loop, control_window=CONTROL_WINDOW,
                   plan_out=plan_on)

    batch_off = replay_plan_batched(plan_off, sim_seeds=sim_seeds,
                                    window_ticks=CONTROL_WINDOW)
    batch_on = replay_plan_batched(plan_on, sim_seeds=sim_seeds,
                                   window_ticks=CONTROL_WINDOW)

    p99_off = [e["p99_rt"] for e in batch_off["seeds"]]
    p99_on = [e["p99_rt"] for e in batch_on["seeds"]]
    std = lambda xs: (_mean([(x - _mean(xs)) ** 2 for x in xs])) ** 0.5
    wins = sum(on < off for on, off in zip(p99_on, p99_off))
    per_seed = [{"sim_seed": int(s), "p99_off": off, "p99_on": on,
                 "win": bool(on < off)}
                for s, off, on in zip(sim_seeds, p99_off, p99_on)]

    # the replay entry that reuses the reference run's sim seed must land
    # on the reference p99 — the parity proof that the scanned core and
    # the shell-driven run are the same simulation
    ref_entry = next(e for e in batch_off["seeds"]
                     if e["sim_seed"] == ref_seed)
    parity_rel = (abs(ref_entry["p99_rt"] - baseline[0].p99_rt)
                  / max(baseline[0].p99_rt, 1e-9))
    # the bar: 20+ vmapped seeds in less wall-clock than the 2-seed loop
    speedup = python_wall / max(batch_off["wall_s"], 1e-9)
    vmap_per_seed = batch_off["wall_s"] / len(sim_seeds)
    python_per_seed = python_wall / 2

    out.append((
        "control.batched.python_loop", python_wall * 1e6,
        f"seeds=2;wall_s={python_wall:.1f};"
        f"p99={_mean([r.p99_rt for r in baseline]):.2f}",
    ))
    out.append((
        "control.batched.vmap", batch_off["wall_s"] * 1e6,
        f"seeds={len(sim_seeds)};wall_off_s={batch_off['wall_s']:.1f};"
        f"wall_on_s={batch_on['wall_s']:.1f};"
        f"windows={batch_off['num_windows']};"
        f"per_seed_s={vmap_per_seed:.1f}",
    ))
    out.append((
        "control.batched.speedup", 0.0,
        f"python_2seed_s={python_wall:.1f};"
        f"vmap_{len(sim_seeds)}seed_s={batch_off['wall_s']:.1f};"
        f"speedup={speedup:.1f}x;"
        f"faster_than_python={batch_off['wall_s'] < python_wall};"
        f"per_seed_vmap_s={vmap_per_seed:.1f};"
        f"per_seed_python_s={python_per_seed:.1f}",
    ))
    out.append((
        "control.batched.parity", 0.0,
        f"ref_p99={baseline[0].p99_rt:.2f};"
        f"replay_p99={ref_entry['p99_rt']:.2f};"
        f"rel_diff={parity_rel:.4f};parity_ok={parity_rel < 0.01}",
    ))
    out.append((
        "control.batched.winloss", 0.0,
        f"p99_off={_mean(p99_off):.2f}+/-{std(p99_off):.2f};"
        f"p99_on={_mean(p99_on):.2f}+/-{std(p99_on):.2f};"
        f"wins={wins}/{len(sim_seeds)}",
    ))

    json_doc["batched"] = {
        "sim_seeds": [int(s) for s in sim_seeds],
        "trace": PROACTIVE_TRACE,
        "num_windows": batch_off["num_windows"],
        "python_baseline": {
            "seeds": [ref_seed, ref_seed + 1],
            "wall_s": python_wall,
            "p99": [r.p99_rt for r in baseline],
            "core": "per-window Python loop",
        },
        "vmap_wall_off_s": batch_off["wall_s"],
        "vmap_wall_on_s": batch_on["wall_s"],
        "vmap_per_seed_s": vmap_per_seed,
        "python_per_seed_s": python_per_seed,
        "speedup_vs_python": speedup,
        "faster_than_python": batch_off["wall_s"] < python_wall,
        "p99_off_mean": _mean(p99_off), "p99_off_std": std(p99_off),
        "p99_on_mean": _mean(p99_on), "p99_on_std": std(p99_on),
        "wins": int(wins), "losses": int(len(sim_seeds) - wins),
        "per_seed": per_seed,
        "parity_rel_diff": parity_rel,
        "parity_ok": parity_rel < 0.01,
    }


def _chain_check(trace: Trace) -> dict:
    """ISSUE-6 acceptance bar, evaluated on the trace alone: every executed
    action has a Planned event, and every non-proactive one whose next
    window elapsed has a Verified/Discarded resolution."""
    chains = action_chains(trace)
    executed = [c for c in chains if c["executed"] is not None]
    last_w = trace.last_window()
    missing_planned = [c["action_id"] for c in executed
                       if c["planned"] is None]
    missing_verified = [
        c["action_id"] for c in executed
        if not c["executed"].proactive and c["executed"].window < last_w
        and c["verified"] is None
    ]
    return {
        "executed": len(executed),
        "missing_planned": missing_planned,
        "missing_verified": missing_verified,
        "chain_ok": not missing_planned and not missing_verified,
    }


def _proactive_axis(predictor, seeds, out, json_doc, trace_path=None):
    # "unified" is the full ClusterView/ForecastService stack: ICO-F
    # admission AND proactive mitigation consuming ONE shared service, so
    # placement and runtime correction price contention with the same
    # projection (the other modes keep plain ICO placement)
    modes = ("off", "reactive", "proactive", "unified")
    rows = []
    fcals = []
    for seed_idx, (trace_seed, sim_seed) in enumerate(seeds):
        pods, gaps = bursty_trace(seed=trace_seed, **PROACTIVE_TRACE)
        row = {"trace_seed": trace_seed, "sim_seed": sim_seed}
        for mode in modes:
            sched_name = "ICO-F" if mode == "unified" else "ICO"
            sched = make_schedulers(predictor, forecast=True)[sched_name]
            cfg = scheduler_loop_config(
                sched_name, proactive=(mode in ("proactive", "unified")))
            # the shared service carries the loop profile's gates/horizon —
            # an external service's own config governs the projection
            svc = (ForecastService(cfg.forecast, cfg.horizon)
                   if mode == "unified" else None)
            loop = None
            if mode != "off":
                loop = ControlLoop(
                    InterferenceQuantifier(predictor.predict), cfg,
                    forecast_service=svc,
                )
            # trace the first seed's unified run (the full stack: admission
            # breakdowns, hotspot channels, action chains, trust-gate flips)
            rec = (TraceRecorder() if trace_path and seed_idx == 0
                   and mode == "unified" else None)
            r = run_experiment(sched, pods, gaps,
                               num_nodes=12, seed=sim_seed, control_loop=loop,
                               forecast=svc, control_window=CONTROL_WINDOW,
                               recorder=rec)
            if rec is not None:
                n_events = rec.save(trace_path)
                check = _chain_check(Trace(rec.events))
                out.append((
                    "control.trace",
                    0.0,
                    f"path={trace_path};events={n_events};"
                    f"executed={check['executed']};"
                    f"chain_ok={check['chain_ok']}",
                ))
                json_doc["trace"] = {"path": trace_path,
                                     "events": n_events, **check}
            row[mode] = {"p99_rt": r.p99_rt, "avg_rt": r.avg_rt,
                         "mitigations": r.mitigations,
                         "proactive_mitigations": r.proactive_mitigations}
            if mode == "proactive" and loop is not None:
                row["proactive_flags"] = loop.stats.proactive_flagged
                if loop.forecaster is not None:
                    fcal = loop.forecaster.calibration_error()
                    row["forecast_calibration"] = fcal
                    fcals.append(fcal)
        rows.append(row)
        out.append((
            f"control.proactive.ICO.seed{trace_seed}",
            0.0,
            f"p99_off={row['off']['p99_rt']:.2f};"
            f"p99_reactive={row['reactive']['p99_rt']:.2f};"
            f"p99_proactive={row['proactive']['p99_rt']:.2f};"
            f"p99_unified={row['unified']['p99_rt']:.2f};"
            f"pro_actions={row['proactive']['proactive_mitigations']};"
            f"win={row['proactive']['p99_rt'] <= row['reactive']['p99_rt']}",
        ))
    means = {m: _mean([r[m]["p99_rt"] for r in rows]) for m in modes}
    out.append((
        "control.proactive.summary",
        0.0,
        f"mean_p99_off={means['off']:.2f};"
        f"mean_p99_reactive={means['reactive']:.2f};"
        f"mean_p99_proactive={means['proactive']:.2f};"
        f"mean_p99_unified={means['unified']:.2f};"
        f"proactive_beats_reactive={means['proactive'] <= means['reactive']};"
        f"forecast_calibration={_mean(fcals) if fcals else float('nan'):.3f}",
    ))
    json_doc["proactive"] = {
        "control_window": CONTROL_WINDOW,
        "trace": PROACTIVE_TRACE,
        "rows": rows,
        "mean_p99": means,
        "forecast_calibration": _mean(fcals) if fcals else None,
    }


def run(fast: bool = True, json_path: str | None = None,
        proactive: bool = False, trace_path: str | None = None):
    num_placements = 80 if fast else 250
    # (trace_seed, sim_seed) pairs: the acceptance bar is ICO+control
    # beating plain ICO on p99 at >= 2 independent seeds
    seeds = [(0, 11), (1, 12)] if fast else [(0, 11), (1, 12), (2, 13)]
    rf_seed = 7
    predictor = train_default_predictor(seed=rf_seed, num_placements=num_placements)

    out: list = []
    json_doc: dict = {"seeds": seeds, "fast": fast}
    _profile_grid(predictor, seeds, out, json_doc)
    _batched_axis(out, json_doc)
    if proactive:
        _proactive_axis(predictor, seeds, out, json_doc,
                        trace_path=trace_path)

    if json_path:
        with open(json_path, "w") as f:
            json.dump(json_doc, f, indent=2)
    return out


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
    fast = "--full" not in sys.argv
    json_path = _flag_value(sys.argv, "--json", "BENCH_control.json")
    trace_path = _flag_value(sys.argv, "--trace", "BENCH_control_trace.jsonl")
    for row in run(fast=fast, json_path=json_path,
                   proactive="--proactive" in sys.argv,
                   trace_path=trace_path):
        print(",".join(map(str, row)))
