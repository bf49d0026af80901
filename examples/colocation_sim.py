"""Co-located train + serve under ICO: the paper's scenario with the
framework's own workloads as the pods.

Online pods = LM serving jobs (repro.serve) whose declared QPS drives
their simulated resource demand; offline pods = training jobs
(repro.train).  The ICO scheduler places both on the simulated cluster;
we then inject a real ServeEngine + real train steps for one node to show
the runqlat metric flowing end-to-end from framework telemetry into
Eq. (1)/(3).

Every admission runs with a ``TraceRecorder`` attached, so after the
stream is placed the demo replays one decision from the trace: the full
per-node Eq. (4)-(6) breakdown behind "why did this pod land there".

Run: PYTHONPATH=src python examples/colocation_sim.py
(``--selftest`` runs a seconds-scale smoke instead: one traced admission
on a 2-node cluster, no predictor training, no model init.)
"""
import sys

import numpy as np
import jax
import jax.numpy as jnp

from repro.cluster.experiment import train_default_predictor, make_schedulers
from repro.cluster.simulator import Cluster
from repro.cluster.workloads import Pod, ONLINE_PROFILES, OFFLINE_PROFILES
from repro.configs import get_smoke_config
from repro.core import metric
from repro.models import model as M
from repro.obs import Trace, TraceRecorder
from repro.obs.explain import explain_pod
from repro.serve import ServeEngine


def main():
    print("== training the Eq.(3) predictor on simulated telemetry ==")
    predictor = train_default_predictor(seed=3, num_placements=120)
    ico = make_schedulers(predictor)["ICO"]
    rec = TraceRecorder()
    ico.recorder = rec

    cluster = Cluster(num_nodes=6, seed=3)
    cluster.rollout(30)
    rec.begin_window(cluster.t)

    print("== submitting a mixed train+serve pod stream through ICO ==")
    rng = np.random.default_rng(3)
    placements = []
    for i in range(14):
        if i % 3 != 2:  # two serving pods per training pod
            prof = ONLINE_PROFILES["web_search"]
            qps = float(rng.uniform(100, 600))
            pod = Pod("web_search", qps, True)
            pod.cpu_demand = prof.cpu_per_qps * qps + prof.cpu_base
            pod.mem_demand = prof.mem_per_qps * qps + prof.mem_base
            kind = f"serve(qps={qps:.0f})"
        else:
            prof = OFFLINE_PROFILES["in_memory_analytics"]
            cores = float(rng.choice(prof.cores_choices))
            pod = Pod("in_memory_analytics", 0.0, False, duration=600)
            pod.cpu_demand = cores
            pod.mem_demand = cores * prof.mem_per_core
            kind = f"train(cores={cores:.0f})"
        node = ico.select_node(pod, cluster.view())
        ok = node >= 0 and cluster.place(pod, node)
        rec.resolve_admission(uid=pod.uid if ok else -1, placed=ok)
        placements.append((kind, node if ok else -1))
        cluster.rollout(10)
        rec.begin_window(cluster.t)
        print(f"   pod {i:2d} {kind:18s} -> node {node if ok else 'REJECTED'}")

    trace = Trace(rec.events)
    placed = trace.query("admission", placed=True)
    if placed:
        print("\n== why did the first pod land there?  (from the trace) ==")
        print(explain_pod(trace, placed[0].uid))

    view = cluster.view()
    print("\n== node utilization / interference after placement ==")
    for n in range(cluster.n):
        node_hist = view.online_hists[n].sum(0) + view.offline_hists[n].sum(0)
        avg = float(metric.avg_runqlat(jnp.asarray(node_hist)))
        print(f"   node {n}: cpu={view.cpu_util[n] * 100:5.1f}% "
              f"mem={view.mem_util[n] * 100:5.1f}% runqlat_avg={avg:7.1f}u")

    print("\n== real framework telemetry: ServeEngine runqlat -> Eq.(1) ==")
    cfg = get_smoke_config("smollm-135m")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_batch=4)
    for i in range(8):
        eng.submit(rng.integers(0, cfg.vocab_size, size=(8,)), max_new_tokens=4)
    stats = eng.run()
    print(f"   served {stats['finished']} requests, "
          f"avg latency {stats['avg_latency'] * 1e3:.0f}ms, "
          f"admission runqlat avg {stats['runqlat_avg']:.1f}u")
    # this histogram is exactly what the Data Collection Module exports
    from repro.core.interference import node_interference
    intf = float(node_interference(
        jnp.asarray(stats["runqlat_hist"])[None, None, :],
        jnp.zeros((1, 1, 200)),
    )[0])
    print(f"   -> node interference contribution (Eq.1): {intf:.4f}")


def selftest() -> None:
    """Seconds-scale smoke for CI/dev loops: one traced ICO admission on a
    tiny cluster, skipping predictor training and the real ServeEngine."""
    from repro.core import ICOScheduler, InterferenceQuantifier

    sched = ICOScheduler(InterferenceQuantifier(lambda X: X[:, 21]))
    rec = TraceRecorder()
    sched.recorder = rec
    cluster = Cluster(num_nodes=2, seed=0)
    cluster.rollout(3)
    rec.begin_window(cluster.t)
    prof = ONLINE_PROFILES["web_search"]
    pod = Pod("web_search", 200.0, True)
    pod.cpu_demand = prof.cpu_per_qps * 200.0 + prof.cpu_base
    pod.mem_demand = prof.mem_per_qps * 200.0 + prof.mem_base
    node = sched.select_node(pod, cluster.view())
    assert node >= 0 and cluster.place(pod, node), "admission failed"
    rec.resolve_admission(uid=pod.uid, placed=True)
    assert Trace(rec.events).query("admission", placed=True)
    print("colocation_sim selftest: ok (1 admission traced)")


if __name__ == "__main__":
    if "--selftest" in sys.argv:
        selftest()
    else:
        main()
