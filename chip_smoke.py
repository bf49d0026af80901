"""Drive the orchestrator's main path once on a TPU, at a real cluster size.

Smoke output, not benchmark numbers: every wall printed here is one run
on whatever chip JAX found, compile included (shorter when the persistent
compile cache is warm).

One chip (no arguments):

* Phase 1, live path.  ``run_experiment`` admits a bursty trace of 1,000
  online pods plus offline waves into a 1,000-node ``make_fleet`` fleet
  through ICO-F (which takes the top-k prefilter at this size), a shared
  ``ForecastService`` and a proactive ``ControlLoop``, and records its plan.
  It fails unless every online pod was admitted, the detector and the
  forecaster folded telemetry, and at least one mitigation was applied.
* Phase 2, replay.  ``replay_plan_batched`` replays the recorded plan under
  4 seeds, phase 1's own sim seed among them.  Every seed's RT statistics
  must be finite, and the replay under phase 1's seed must reproduce that
  run's average and p99 RT to a relative ``RT_RTOL``: the batched engine
  checked against the live rollout path on the chip.

``--chips 4``: only the seed-sharded replay.  A synthetic 1,000-node plan
(``bench_rollout_scale._synthetic_plan``) is replayed with 8 seeds on a
4-device seed mesh and on one device, in one process; the outputs must be
bitwise equal.

Every input is generated from ``--seed``.  Without a TPU the script exits
non-zero and prints no result line.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

    python chip_smoke.py [--seed 0] [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

NODES = 1000
CONTROL_WINDOW = 40
REPLAY_SEEDS = 4
SHARD_SEEDS = 8
SHARD_DAYS = 1.0
RT_RTOL = 1e-5   # replay under the live run's seed vs that run


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def tpu_devices():
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU; JAX found {devices[0].platform} "
              f"devices only", file=sys.stderr)
        sys.exit(2)
    return devices


def live_phase(seed: int, nodes: int = NODES, num_online: int = NODES,
               num_bursts: int = 6, jobs_per_burst: int = 32,
               predictor_placements: int = 250):
    """Phase 1; returns (the recorded plan, its result, failures)."""
    from repro.cluster.experiment import (bursty_trace, run_experiment,
                                          train_default_predictor)
    from repro.cluster.fleet import DEFAULT_MIX, make_fleet
    from repro.control import ControlLoop, ForecastService, \
        scheduler_loop_config
    from repro.core import ICOFScheduler, InterferenceQuantifier

    t0 = time.time()
    fleet = make_fleet(nodes, DEFAULT_MIX, seed=seed)
    predictor = train_default_predictor(seed=seed,
                                        num_placements=predictor_placements)
    log(f"setup: fleet of {nodes} nodes, RF predictor trained "
        f"({time.time() - t0:.1f} s)")

    quantifier = InterferenceQuantifier(predictor.predict)
    cfg = scheduler_loop_config("ICO-F", proactive=True)
    service = ForecastService(cfg.forecast, cfg.horizon)
    loop = ControlLoop(quantifier, cfg, forecast_service=service)
    pods, gaps = bursty_trace(num_online=num_online, num_bursts=num_bursts,
                              jobs_per_burst=jobs_per_burst, seed=seed)
    log(f"trace: {num_online} online pods, {num_bursts} offline waves of "
        f"{jobs_per_burst} jobs, {sum(gaps)} ticks of arrivals")

    plan: dict = {}
    t0 = time.time()
    res = run_experiment(ICOFScheduler(quantifier), pods, gaps, seed=seed,
                         fleet=fleet, control_loop=loop, forecast=service,
                         control_window=CONTROL_WINDOW, plan_out=plan)
    wall = time.time() - t0

    stats = loop.stats
    # every online admission logs one place_on; a scale-out replica
    # logs one more
    online_admitted = (sum(e[0] == "place_on" for e in plan["log"])
                       - stats.by_kind.get("scale_out", 0))
    folds = int(service.forecaster.count.max())
    timers = {k: round(v, 3) for k, v in sorted(loop.timers.totals.items())}
    log(f"live (smoke output, not a benchmark): wall {wall:.1f} s, "
        f"t_end {plan['t_end']:.0f} ticks")
    log(f"  placed {res.placed} (online {online_admitted}), rejected "
        f"{res.rejected}, queued retries {res.queued_retries}")
    log(f"  mitigations {res.mitigations} (proactive "
        f"{res.proactive_mitigations}) by kind {stats.by_kind}; hotspots "
        f"flagged {stats.hotspots_flagged}, loop steps {stats.steps}, "
        f"forecaster folds {folds}")
    log(f"  p99 RT {res.p99_rt:.4f}, avg RT {res.avg_rt:.4f}")
    log(f"  phase walls (s): {timers}")

    failures = []
    if online_admitted < num_online:
        failures.append(f"only {online_admitted} of {num_online} online "
                        f"pods admitted")
    if stats.steps == 0 or timers.get("detect", 0.0) <= 0.0:
        failures.append("the detector never ran")
    if folds == 0:
        failures.append("the forecaster folded no window")
    if res.mitigations < 1:
        failures.append("no mitigation was applied")
    if not res.p99_rt == res.p99_rt:
        failures.append("p99 RT is not finite")
    return plan, res, failures


def replay_phase(plan: dict, ref, seed: int, num_seeds: int = REPLAY_SEEDS):
    """Phase 2: the recorded plan replayed under ``num_seeds`` seeds, the
    first of them phase 1's own, whose result ``ref`` it must reproduce."""
    import jax

    from repro.cluster.experiment import replay_plan_batched

    sim_seeds = tuple(range(seed, seed + num_seeds))
    t0 = time.time()
    run = replay_plan_batched(plan, sim_seeds=sim_seeds)
    log(f"replay: {len(sim_seeds)} seeds x {run['num_windows']} windows "
        f"({run['padded_windows']} padded), wall {time.time() - t0:.1f} s "
        f"incl. compile (smoke output)")
    failures = []
    for got in run["seeds"]:
        log(f"  seed {got['sim_seed']}: avg RT {got['avg_rt']!r}, p99 "
            f"{got['p99_rt']!r}, hot windows {got['hot_windows']}")
        if not all(math.isfinite(got[k])
                   for k in ("avg_rt", "p90_rt", "p99_rt")):
            failures.append(f"seed {got['sim_seed']}: RT statistics are "
                            f"not finite")
    same = next(g for g in run["seeds"] if g["sim_seed"] == seed)
    for k in ("avg_rt", "p99_rt"):
        want = getattr(ref, k)
        rel = abs(same[k] - want) / abs(want)
        log(f"  {k} under the live run's seed {seed}: replay {same[k]!r}, "
            f"live {want!r}, relative gap {rel!r}")
        if not rel <= RT_RTOL:
            failures.append(f"replay {k} {same[k]!r} differs from the live "
                            f"run's {want!r} by {rel!r} (> {RT_RTOL})")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    return failures


def shard_phase(seed: int, chips: int, nodes: int = NODES,
                num_seeds: int = SHARD_SEEDS, days: float = SHARD_DAYS):
    """--chips N: seed-sharded replay vs one device, bitwise."""
    import jax
    import numpy as np

    from benchmarks.bench_rollout_scale import _synthetic_plan
    from repro.cluster import state as cstate
    from repro.cluster.experiment import replay_inputs
    from repro.launch.mesh import make_seed_mesh

    mesh_ids = {d.id for d in make_seed_mesh(chips).devices.flat}
    log(f"seed mesh: {len(mesh_ids)} distinct devices {sorted(mesh_ids)}")
    failures = [] if len(mesh_ids) == chips else [
        f"the seed mesh holds {len(mesh_ids)} devices, not {chips}"]

    event_log, t_end = _synthetic_plan(nodes, days, seed=seed)
    plan = {"log": event_log, "t_end": t_end, "num_nodes": nodes}
    inp = replay_inputs(plan, tuple(range(seed, seed + num_seeds)))
    outs = {}
    for devices in (chips, None):
        t0 = time.time()
        _, outs[devices] = cstate.batched_rollout(
            inp["state"], inp["profiles"], 0.0, inp["keys"], inp["events"],
            fleet=inp["fleet"], devices=devices)
        jax.block_until_ready(outs[devices])
        log(f"replay devices={devices}: {num_seeds} seeds x "
            f"{inp['padded_windows']} windows x {nodes} nodes, wall "
            f"{time.time() - t0:.1f} s incl. compile (smoke output)")
    spread = len(outs[chips]["rt"].sharding.device_set)
    log(f"sharded RT output lives on {spread} devices")
    if spread != chips:
        failures.append(f"sharded output on {spread} devices, not {chips}")
    for name in sorted(outs[None]):
        same = np.array_equal(np.asarray(outs[chips][name]),
                              np.asarray(outs[None][name]))
        log(f"  {name}: bitwise equal {same}")
        if not same:
            failures.append(f"{name} differs between {chips} devices and 1")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1,
                    help="4: run only the seed-sharded replay across chips")
    args = ap.parse_args(argv)

    devices = tpu_devices()
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    dev = devices[0]
    log(f"platform {dev.platform}, device_kind {dev.device_kind}, "
        f"device count {len(devices)}, compile cache {cache_dir}")

    t0 = time.time()
    if args.chips > 1:
        failures = shard_phase(args.seed, args.chips)
    else:
        plan, res, failures = live_phase(args.seed)
        if not failures:
            failures = replay_phase(plan, res, args.seed)
    log(f"total wall {time.time() - t0:.1f} s")
    if failures:
        for f in failures:
            print(f"chip_smoke: FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
