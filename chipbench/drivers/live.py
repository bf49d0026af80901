"""Live driver: back-to-back ``run_experiment`` calls, each a fresh day.

Each call admits a fresh ``bursty_trace`` (online services, then waves of
batch jobs) into the configuration's cluster through ICO-F over the Eq. 3
random forest, with the proactive ``ControlLoop`` and one shared
``ForecastService`` stepping every ``control_window`` ticks, on the
scanned rollout path and with no trace recorder: the program's own path,
as ``bench_control``'s unified mode runs it.  The scheduler's decision log
and the loop's outcome records are on; they observe and change nothing.

Set-up trains the random forest from ``--seed``, checks the program's
scheduler and loop settings against the configuration, and warms every
shape a day brings with one whole call.  Call ``i`` draws its trace and
simulation seeds from ``(--seed, i)``.

Workload keys: ``trace`` (``bursty_trace``'s parameters) and
``settle_ticks``; the control window, retry queue and predictor come from
the configuration.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import rel_gap
from chipbench.reference import admit as ref_admit
from chipbench.reference import sim as ref

STATS = ("avg_rt", "p90_rt", "p99_rt")
UTIL = ("cpu_util_std", "mem_util_std")
TERMS = ("utiliz_cpu", "utiliz_mem", "intf_nodes")   # Eqs. 5-6 and Eq. 1
COMPILE_EVENT = "backend_compile"


def _check_settings(config: dict) -> None:
    """The program's scheduler and loop settings are the ones the
    configuration states (the reference reads the configuration)."""
    from repro.cluster.fleet import MACHINE_CLASSES
    from repro.control import scheduler_loop_config
    from repro.core import SchedulerConfig
    from repro.core.interference import InterferenceWeights

    adm, ctl = config["admission"], config["control"]
    loop = scheduler_loop_config(ctl["profile"],
                                 proactive=ctl["loop"]["proactive"])
    stated = {
        "machine class": (dataclasses.asdict(
            MACHINE_CLASSES[config["machine_class"]["name"]]),
            config["machine_class"]),
        "scheduler": (dataclasses.asdict(SchedulerConfig()),
                      adm["scheduler_config"]),
        "weights": (dataclasses.asdict(InterferenceWeights()),
                    adm["weights"]),
        "loop": (dataclasses.asdict(loop), ctl["loop"]),
        "loop detector": (dataclasses.asdict(loop.detector),
                          {**config["detector"], **ctl["loop_detector"]}),
        "policy": (dataclasses.asdict(loop.policy), ctl["policy"]),
        "forecast": (dataclasses.asdict(loop.forecast), ctl["forecast"]),
    }
    for what, (prog, cfg) in stated.items():
        for k, v in cfg.items():
            if prog[k] != v:
                raise ValueError(f"{what}: the program's {k}={prog[k]!r}, "
                                 f"the configuration states {v!r}")


def _warm_shell(num_nodes: int) -> None:
    """Compile what a day may need that the warm call may not reach: the
    rollout of every chunk count a window can take, and every mutation
    the loop can make."""
    from repro.cluster import workloads as W
    from repro.cluster.simulator import Cluster
    from repro.cluster.workloads import Pod

    cluster = Cluster(num_nodes=num_nodes, seed=0)
    for chunks in range(1, 5):
        cluster.rollout_scan(chunks * cluster.CHUNK)
    on = Pod(W.ONLINE_NAMES[0], 200.0, True)
    off = Pod(W.OFFLINE_NAMES[0], 0.0, False, duration=100)
    off.cpu_demand = 4.0
    cluster.place(on, 0)
    cluster.place(off, 0)
    cluster.migrate(on.uid, 1)
    cluster.resize(on.uid, qps=100.0)
    cluster.resize(off.uid, cores=2.0)
    cluster.remove(off.uid)
    cluster.view()


def _check_program() -> None:
    """Exit at once on a program without the decision log, the outcome
    records or the per-run phases, which ``correct`` and the readers
    need."""
    from repro.cluster.experiment import ExperimentResult
    from repro.control import ControlLoop
    from repro.core import ICOScheduler

    fields = {f.name for f in dataclasses.fields(ExperimentResult)}
    if not ({"offers", "offers_rejected", "phases"} <= fields
            and "decisions" in vars(ICOScheduler(None))
            and "_record_outcomes" in vars(ControlLoop)):
        raise RuntimeError("this program keeps no admission decision log or "
                           "loop outcome records; the live cell needs both")


def setup(cell, warm: bool = True) -> dict:
    from repro.cluster.experiment import train_default_predictor
    from repro.core import InterferenceQuantifier
    from repro.core.interference import InterferenceWeights

    _check_program()
    config = cell.config
    _check_settings(config)
    adm = config["admission"]
    rf = adm["predictor"]
    predictor = train_default_predictor(
        seed=cell.seeds(0)[0], num_placements=rf["training_placements"])
    if (predictor.n_estimators, predictor.max_depth) != (
            rf["n_estimators"], rf["max_depth"]):
        raise ValueError("the program's random forest is not the one the "
                         "configuration states")
    compiles = [0]

    def listen(event, secs, **kw):
        if COMPILE_EVENT in event:
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    ctx = {"cell": cell, "listen": listen, "compiles": compiles,
           "quantifier": InterferenceQuantifier(
               predictor.predict, InterferenceWeights(**adm["weights"]))}
    if warm:
        _warm_shell(config["nodes"])
        _day(ctx, *cell.seeds(1, count=2))
    return ctx


def _day(ctx, trace_seed: int, sim_seed: int) -> dict:
    """One run_experiment call on a fresh trace; returns its record."""
    from repro.cluster.experiment import bursty_trace, run_experiment
    from repro.control import ControlLoop, ForecastService, \
        scheduler_loop_config
    from repro.core import ICOFScheduler, SchedulerConfig

    cell = ctx["cell"]
    config, wl = cell.config, cell.workload
    adm, ctl = config["admission"], config["control"]
    q = ctx["quantifier"]
    cfg = scheduler_loop_config(ctl["profile"],
                                proactive=ctl["loop"]["proactive"])
    service = ForecastService(cfg.forecast, cfg.horizon)
    loop = ControlLoop(q, cfg, forecast_service=service)
    sched = ICOFScheduler(q, SchedulerConfig(**adm["scheduler_config"]),
                          w_f=adm["w_f"])
    sched.decisions, loop.outcomes = [], []
    pods, gaps = bursty_trace(seed=trace_seed, **wl["trace"])
    plan: dict = {}
    c0 = ctx["compiles"][0]
    t0 = time.perf_counter()
    res = run_experiment(sched, pods, gaps, num_nodes=config["nodes"],
                         seed=sim_seed, settle_ticks=wl["settle_ticks"],
                         control_loop=loop, forecast=service,
                         control_window=ctl["control_window"],
                         retry_limit=adm["retry_limit"],
                         retry_attempts=adm["retry_attempts"],
                         plan_out=plan)
    t1 = time.perf_counter()
    return {"start": t0, "end": t1, "trace_seed": trace_seed,
            "sim_seed": sim_seed,
            "node_ticks": plan["t_end"] * plan["num_nodes"],
            "pods": len(pods), "rejected": res.rejected,
            "offers": res.offers, "offers_rejected": res.offers_rejected,
            "phases": res.phases, "windows": len(loop.outcomes),
            "compiles": ctx["compiles"][0] - c0,
            "answers": {k: getattr(res, k) for k in STATS + UTIL},
            "plan": {k: plan[k] for k in ("log", "t_end", "num_nodes",
                                          "settle_ticks")},
            "decisions": sched.decisions, "outcomes": loop.outcomes}


def call(ctx, i: int) -> dict:
    return _day(ctx, *ctx["cell"].seeds(2, i, count=2))


def end_to_end(ctx, records) -> dict:
    span = records[-1]["end"] - records[0]["start"]
    return {"replay_node_ticks_per_s":
            (sum(r["node_ticks"] for r in records) / span, "node-ticks/s")}


def attempted(records) -> tuple[int, int]:
    """Pods offered, and those never placed (the retry queue gave up)."""
    return (sum(r["pods"] for r in records),
            sum(r["rejected"] for r in records))


def release(ctx) -> None:
    jax.monitoring.unregister_event_duration_listener(ctx.pop("listen"))
    ctx.pop("quantifier")


def _windows(rec) -> list[tuple[float, float]]:
    return [(o["t"] - o["window_ticks"], o["t"]) for o in rec["outcomes"]]


def reference(config: dict, rec: dict, dtype=jnp.float32) -> dict:
    """The reference's replay of one call, and its answers."""
    plan = rec["plan"]
    windows = _windows(rec)
    with jax.default_matmul_precision("highest"):
        sim = ref_admit.replay(config, plan, rec["sim_seed"], windows,
                               rec["decisions"], dtype=dtype)
    t_end = plan["t_end"]
    arrival = np.array([b <= t_end - plan["settle_ticks"]
                        for _, b in windows])
    answers = dict(ref.rt_stats(sim["rt"]))
    for k, u in (("cpu_util_std", "cpu_util"), ("mem_util_std", "mem_util")):
        answers[k] = float((100 * sim[u][arrival]).std(axis=1).mean())
    return {"sim": sim, "answers": answers}


def compare(config: dict, rec: dict, want: dict) -> dict:
    """The numbers compared for one call (see the workload's limits)."""
    sim = want["sim"]
    got = rec["answers"]
    rt = max(rel_gap(got[k], want["answers"][k]) for k in STATS)
    util = max(rel_gap(got[k], want["answers"][k]) for k in UTIL)
    hot = np.zeros_like(sim["hot"])
    for i, o in enumerate(rec["outcomes"]):
        hot[i, o["hot"]] = True
    terms = 0.0
    for d, w in zip(rec["decisions"], sim["offers"], strict=True):
        seen = np.isfinite(np.asarray(d["intf_nodes"], np.float64))
        for k in TERMS:
            terms = max([terms] + [rel_gap(float(a), float(b)) for a, b
                                   in zip(np.asarray(d[k])[seen],
                                          np.asarray(w[k])[seen])])
    choice = sum(not ref_admit.choice_ok(config, d) for d in rec["decisions"])
    cooled = ref_admit.cooled_slots(config, rec["outcomes"], rec["plan"]["log"])
    unhandled = ref_admit.outcome_faults(config, rec["outcomes"],
                                         rec["plan"]["log"], sim["off_active"],
                                         cooled)
    # the windows tile [30, t_end): anything else is a run the reference
    # cannot replay window for window
    starts, ends = zip(*_windows(rec))
    tiled = (starts[0] == ref.WARMUP_TICKS and starts[1:] == ends[:-1]
             and ends[-1] == rec["plan"]["t_end"])
    return {"rt_stat_gap": rt, "util_std_gap": util,
            "admit_terms_gap": terms,
            "hot_windows_gap": float((hot != sim["hot"]).sum()),
            "admit_choice_gap": float(choice),
            "unhandled_hot_nodes": float(unhandled),
            "invalid_events": float(sim["invalid"] + (not tiled))}


def check(ctx, records, dtype=None) -> dict:
    """Replay every call of the window through the plain reference and
    keep the widest reading of each number."""
    config = ctx["cell"].config
    out: dict = {}
    for rec in records:
        got = compare(config, rec, reference(config, rec,
                                             dtype or jnp.float32))
        out = {k: max(v, out.get(k, v)) for k, v in got.items()}
    return out


def as_reference(ctx, records, dtype) -> list:
    """The records with the program's answers replaced by the reference's,
    computed in ``dtype`` (the control): RT and utilization statistics,
    each window's flags and each offer's Eq. 1 and Eqs. 5-6 terms."""
    config = ctx["cell"].config
    out = []
    for rec in records:
        want = reference(config, rec, dtype)
        sim = want["sim"]
        outcomes = [dict(o, hot=np.nonzero(sim["hot"][i])[0].tolist())
                    for i, o in enumerate(rec["outcomes"])]
        decisions = [dict(d, **w) for d, w
                     in zip(rec["decisions"], sim["offers"], strict=True)]
        out.append(dict(rec, answers=want["answers"], outcomes=outcomes,
                        decisions=decisions))
    return out
