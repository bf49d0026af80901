"""Replay driver: back-to-back ``replay_plan_batched`` calls on one plan.

Set-up generates the cell's plan from ``--seed`` (``traffic/plans.py``)
and warms the one compiled program every call runs.  Each timed call
replays that plan under ``seeds_per_call`` fresh simulation seeds.

Workload keys: ``plan`` (the generator's parameters), ``seeds_per_call``,
``window_ticks`` and ``chips`` (more than one shards the seed axis).
"""
from __future__ import annotations

import time

import numpy as np

from chipbench.harness import rel_gap
from chipbench.reference import sim as ref
from chipbench.traffic import plans

STATS = ("avg_rt", "p90_rt", "p99_rt")
UTIL = ("cpu_util_std", "mem_util_std")


def _fleet(config: dict):
    """The program's fleet for the configuration, checked against the
    machine physics the configuration states (the reference reads those)."""
    from repro.cluster.fleet import MACHINE_CLASSES, Fleet

    mc = config["machine_class"]
    prog = MACHINE_CLASSES[mc["name"]]
    for k, v in mc.items():
        if getattr(prog, k) != v:
            raise ValueError(f"machine class {mc['name']}: the program's "
                             f"{k}={getattr(prog, k)!r}, the configuration "
                             f"states {v!r}")
    if config["fleet"] == "homogeneous":
        return Fleet.homogeneous(config["nodes"], prog)
    return None   # the simulator's built-in std32 nodes


def setup(cell, warm: bool = True) -> dict:
    from repro.cluster.experiment import replay_plan_batched

    wl = cell.workload
    plan = plans.make_plan(cell.config, wl["plan"], cell.seeds(0)[0])
    plan["fleet"] = _fleet(cell.config)
    ctx = {"cell": cell, "plan": plan, "replay": replay_plan_batched,
           "devices": cell.chips if cell.chips > 1 else None}
    if warm:
        _replay(ctx, cell.seeds(1, count=wl["seeds_per_call"]))
    return ctx


def _replay(ctx, sim_seeds) -> dict:
    return ctx["replay"](ctx["plan"], sim_seeds=tuple(sim_seeds),
                         window_ticks=ctx["cell"].workload["window_ticks"],
                         devices=ctx["devices"])


def call(ctx, i: int) -> dict:
    cell = ctx["cell"]
    sim_seeds = cell.seeds(2, i, count=cell.workload["seeds_per_call"])
    t0 = time.perf_counter()
    out = _replay(ctx, sim_seeds)
    t1 = time.perf_counter()
    plan = ctx["plan"]
    span = out["padded_windows"] * cell.workload["window_ticks"]
    return {"start": t0, "end": t1, "sim_seeds": sim_seeds,
            "seeds": out["seeds"], "wall_s": out["wall_s"],
            "node_ticks": len(sim_seeds) * plan["t_end"] * plan["num_nodes"],
            "computed_node_ticks": len(sim_seeds) * span * plan["num_nodes"]}


def end_to_end(ctx, records) -> dict:
    span = records[-1]["end"] - records[0]["start"]
    return {"replay_node_ticks_per_s":
            (sum(r["node_ticks"] for r in records) / span, "node-ticks/s")}


def attempted(records) -> tuple[int, int]:
    return len(records), 0


def release(ctx) -> None:
    ctx.pop("replay")


def check(ctx, records, dtype=None) -> dict:
    """Replay one call of the window, drawn from the seed, through the
    plain reference, every seed of it, and compare its answers."""
    import jax.numpy as jnp

    cell = ctx["cell"]
    pick = np.random.default_rng(cell.seeds(3)[0]).integers(len(records))
    rec = records[int(pick)]
    want = ref.replay_stats(cell.config, ctx["plan"], rec["sim_seeds"],
                            dtype=dtype or jnp.float32)
    return compare(rec["seeds"], want)


def as_reference(ctx, records, dtype) -> list:
    """The records with the program's answers replaced by the
    reference's, computed in ``dtype`` (the control)."""
    return [dict(r, seeds=ref.replay_stats(ctx["cell"].config, ctx["plan"],
                                           r["sim_seeds"],
                                           dtype=dtype)["seeds"])
            for r in records]


def compare(got: list, want: dict) -> dict:
    """The numbers compared: the widest relative gap of the RT statistics
    and of the utilization spread, the widest hot-window count gap, and
    the log entries the reference refused."""
    rt = util = hot = 0.0
    for g, w in zip(got, want["seeds"], strict=True):
        if g["sim_seed"] != w["sim_seed"]:
            raise ValueError("seed order differs from the reference's")
        rt = max([rt] + [rel_gap(g[k], w[k]) for k in STATS])
        util = max([util] + [rel_gap(g[k], w[k]) for k in UTIL])
        hot = max(hot, abs(g["hot_windows"] - w["hot_windows"]))
    return {"rt_stat_gap": rt, "util_std_gap": util,
            "hot_windows_gap": float(hot),
            "invalid_events": float(want["invalid"])}

