"""Scheduler decision latency at scale: Algorithm 1 must stay cheap as the
node count grows (it is on every pod-submission critical path).

The sweep runs every scheduler against heterogeneous ``make_fleet``
views of 128 / 1 000 / 5 000 nodes (5 000 with ``--full``) and reports
mean and p99 per-admission latency.  Past ``SchedulerConfig.candidate_k``
nodes ICO/ICO-F switch to the jit'd top-k prefilter, so their rows are
the sub-linear-scaling evidence the CI gate asserts on (5k p99 within
10x of the 128-node p99); the O(N)-scoring baselines ride along for
contrast.  ``--json PATH`` dumps ``{"rows": ..., "sweep":
{scheduler: {n: {mean_us, p99_us}}}}`` for that gate.

``--timers`` additionally runs a short proactive control loop against a
live simulator and reports the wall-clock split across control-plane
phases (rollout / detect / forecast / plan / verify) from the loop's
``PhaseTimers`` — the baseline ROADMAP item 2's 5k-node latency gate
measures against.  Phase means include JAX dispatch; the first window
carries jit compilation, which is why the split is reported over ~30
windows rather than one.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

from repro.cluster import ClusterView
from repro.cluster.fleet import make_fleet
from repro.core import ICOScheduler, InterferenceQuantifier
from repro.core.baselines import HUPScheduler, LQPScheduler, RoundRobinScheduler
from repro.core.scheduler import ICOFScheduler
from repro.cluster.workloads import Pod

SIZES_FAST = (128, 1000)
SIZES_FULL = (128, 1000, 5000)


def _fleet_view(n: int, seed: int = 0) -> ClusterView:
    """A heterogeneous admission snapshot: per-class capacities and delay
    params from ``make_fleet``, synthetic occupancy at ~5-60%% so every
    node stays feasible and the argmax does real work."""
    fleet = make_fleet(n, seed=seed)
    rng = np.random.default_rng(seed)
    cores, mem = fleet.cores(), fleet.mem_gb()
    hists = np.zeros((n, 4, 200))
    hists[:, :, 20] = rng.integers(1, 50, (n, 4))
    d64 = fleet.delay_params64()
    return ClusterView(
        cpu_cur=rng.uniform(0.05, 0.55, n) * cores,
        cpu_sum=cores,
        mem_cur=rng.uniform(0.05, 0.55, n) * mem,
        mem_sum=mem,
        online_hists=hists,
        offline_hists=np.zeros((n, 4, 200)),
        features=rng.normal(0, 1, (n, 45)),
        online_qps_sum=rng.uniform(0, 500, n),
        node_class=fleet.class_names(),
        fleet=fleet,
        delay_base=d64["base"],
        delay_scale=d64["scale"],
        rho_knee=d64["knee"],
    )


def _schedulers():
    # lightweight linear predictor keeps this a scheduler-cost benchmark
    q = InterferenceQuantifier(lambda x: np.asarray(x)[:, 0] * 0.1)
    return {
        "ICO": ICOScheduler(q),
        "ICO-F": ICOFScheduler(q),
        "HUP": HUPScheduler(q),
        "LQP": LQPScheduler(),
        "RR": RoundRobinScheduler(),
    }


def run(fast: bool = True, timers: bool = False, sweep_out: dict | None = None):
    out = []
    sweep: dict[str, dict[str, dict[str, float]]] = {}
    reps = 20 if fast else 40
    for n in SIZES_FAST if fast else SIZES_FULL:
        view = _fleet_view(n)
        pod = Pod("web_search", 200.0, True)
        pod.cpu_demand, pod.mem_demand = 4.0, 3.0
        for name, sched in _schedulers().items():
            sched.select_node(pod, view)  # warm (jit compile, BLAS init)
            lat = np.empty(reps)
            for r in range(reps):
                t0 = time.perf_counter()
                sel = sched.select_node(pod, view)
                lat[r] = time.perf_counter() - t0
            mean_us = float(lat.mean() * 1e6)
            p99_us = float(np.percentile(lat, 99) * 1e6)
            sweep.setdefault(name, {})[str(n)] = {
                "mean_us": mean_us, "p99_us": p99_us}
            out.append((f"scheduler_latency.{name}.n{n}", mean_us,
                        f"p99_us={p99_us:.1f};selected={sel}"))
    if sweep_out is not None:
        sweep_out.update(sweep)
    if timers:
        _phase_timers(out)
    return out


def _phase_timers(out, windows: int = 30, window_ticks: int = 40):
    """Per-phase wall-clock split of a live proactive control loop.

    A small real cluster (8 nodes, a handful of online pods) driven for
    ``windows`` telemetry windows: the loop's own ``PhaseTimers`` wrap the
    jit'd detector/forecaster/policy calls and the rollout, so the split
    is exactly what a traced experiment's PhaseTimings events carry.
    """
    from repro.cluster.simulator import Cluster
    from repro.cluster.workloads import ONLINE_PROFILES
    from repro.control import ControlLoop, scheduler_loop_config

    q = InterferenceQuantifier(lambda x: np.asarray(x)[:, 0] * 0.1)
    sched = ICOScheduler(q)
    cluster = Cluster(num_nodes=8, seed=5)
    cluster.rollout(30)
    rng = np.random.default_rng(5)
    for _ in range(10):
        name = rng.choice(list(ONLINE_PROFILES))
        prof = ONLINE_PROFILES[name]
        qps = float(rng.uniform(150, 450))
        pod = Pod(name, qps, True)
        pod.cpu_demand = prof.cpu_per_qps * qps + prof.cpu_base
        pod.mem_demand = prof.mem_per_qps * qps + prof.mem_base
        node = sched.select_node(pod, cluster.view())
        if node >= 0:
            cluster.place(pod, node)
        cluster.rollout(10)

    loop = ControlLoop(q, scheduler_loop_config("ICO", proactive=True))
    for _ in range(windows):
        with loop.timers.phase("rollout"):
            cluster.rollout(window_ticks)
        loop.step(cluster)
    for phase, s in sorted(loop.timers.summary().items()):
        out.append((
            f"scheduler_latency.phase.{phase}",
            s["mean_ms"] * 1e3,  # us, like every other row
            f"calls={s['calls']};total_s={s['total_s']:.3f};"
            f"mean_ms={s['mean_ms']:.2f}",
        ))


if __name__ == "__main__":
    from repro.launch.cache import enable_persistent_cache

    enable_persistent_cache()
    sweep: dict = {}
    rows = run(fast="--full" not in sys.argv,
               timers="--timers" in sys.argv, sweep_out=sweep)
    for row in rows:
        print(",".join(map(str, row)))
    if "--json" in sys.argv:
        path = sys.argv[sys.argv.index("--json") + 1]
        with open(path, "w") as f:
            json.dump({"rows": [list(r) for r in rows], "sweep": sweep},
                      f, indent=2)
        print(f"wrote {path}")
