"""Mitigation policy: rank candidate actions per hotspot by predicted
runqlat reduction under a migration-budget constraint.

For every flagged node the policy enumerates one candidate of each action
type (evict / throttle an offline offender, migrate / scale out an online
victim) and estimates the runqlat reduction each would buy:

  * source-side relief comes from the same M/G/1-PS delay curve the
    simulator uses — removing c cores of (burst-weighted) pressure from a
    node at pressure rho is worth delay(rho) - delay(rho - c/cores);
  * pod-side effects reuse the Interference Quantification Module: the
    Random Forest behind Eq. (3) predicts the avg runqlat an online pod
    would see on each candidate destination, so migration destinations are
    chosen by argmin predicted interference, exactly like initial placement.

Victim selection is attribution-first: when the detector supplies per-slot
drift scores (which pod's histogram drifted), offenders and victims are
ranked by their slot's score, with the old node-level heuristics
(cores x burst pressure for offline, QPS for online) demoted to
tie-breakers; without attribution the heuristics apply unchanged.

Candidates across all hotspots are pooled, scored by
``correction[kind] * predicted_reduction - cost_weight * cost``, and
applied greedily until the per-invocation budget is exhausted.  The
per-kind corrections come from the ControlLoop's post-action verification
pass: action kinds whose realized reduction historically under-delivers
their prediction are demoted in the greedy ranking.

Nodes flagged *proactively* (forecast drift, no observed hotspot yet) are
planned the same way with two twists: relief is priced at the node's
forecast pressure rather than its (still unremarkable) current pressure,
and candidate costs are discounted by ``proactive_cost_scale`` — an
ahead-of-time migration drains a pod under light load instead of at the
incident's peak, which is the whole point of acting early.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.cluster import simulator as sim
from repro.cluster.workloads import ONLINE_PROFILES
from repro.core import metric
from repro.control.actions import (
    Action,
    EvictOffline,
    MigrateOnline,
    ScaleOut,
    VerticalResize,
)


@dataclasses.dataclass(frozen=True)
class PolicyConfig:
    budget: float = 16.0          # cost units spendable per control invocation
    cost_weight: float = 1.0      # latency units one cost unit must buy
    evict_cost_per_core: float = 0.8
    migrate_cost: float = 3.0
    scale_out_cost: float = 5.0
    resize_cost: float = 0.5
    throttle_frac: float = 0.5    # vertical resize shrinks cores to this
    min_offline_cores: float = 2.0  # never throttle a job below this; repeated
                                    # re-throttling otherwise compounds
                                    # throttle_frac toward zero cores and
                                    # stretches off_remaining without bound
    cpu_threshold: float = 0.70   # destination feasibility thresholds match the
    mem_threshold: float = 0.80   # scheduler's Eq. (5)/(6) cutoffs
    # Unlike admission, destination demand is NOT headroom-inflated by
    # default (w_d = w_e = 1): runtime rebalancing moves load the cluster
    # is already carrying, and the scenario sweep shows that inflating it
    # (set these to the scheduler's 1.2 to forbid anything ICO would
    # reject) blocks enough good destinations to concentrate migrations
    # on the few coldest nodes and worsen p99.
    w_d: float = 1.0
    w_e: float = 1.0
    max_actions_per_node: int = 2
    min_scale_qps: float = 150.0  # don't split a service below this per replica
    migrate_margin: float = 15.0  # min predicted runqlat gap (src - dst, latency
                                  # units) before moving a pod is worth the churn
    transfer_latency_weight: float = 8.0  # latency units charged per unit of
                                  # topology cost-factor above same-rack when
                                  # ranking destinations: a marginally better
                                  # cross-zone node loses to a same-rack one
                                  # unless its predicted gap covers the bytes
                                  # it must drag over the bottleneck link
    proactive_cost_scale: float = 0.6  # ahead-of-time actions are discounted in
                                       # the greedy ranking: moving a pod BEFORE
                                       # its worst window skips the drain-under-
                                       # pressure cost a reactive move pays
    destination_actions: bool = True   # offer migrate/scale-out at all.  Under
                                       # near-uniform placements (RR, and HUP's
                                       # utilization packing) the predicted
                                       # src-dst gaps are mostly noise, and
                                       # destination-gambling actions stack load
                                       # on nodes about to warm up — the
                                       # RR/HUP profiles keep only source-side
                                       # relief (evict / throttle), which
                                       # cannot churn


def node_delay_curve(rho: np.ndarray, base=None, scale=None,
                     knee=None) -> np.ndarray:
    """The simulator's M/G/1-PS delay curve, reused as the relief model.

    ``base``/``scale``/``knee`` are scalars or (N,) float64 arrays — the
    per-node machine-class parameters from ``view_delay_params`` — and
    default to the homogeneous constants.  Always float64: the relief
    model never widens the kernel's float32 arrays (a double-rounded
    0.05 is not the double 0.05), it rebuilds from Python floats.
    """
    base = sim.RUNQLAT_BASE if base is None else base
    scale = sim.RUNQLAT_SCALE if scale is None else scale
    knee = sim.RHO_EPS if knee is None else knee
    return sim.delay_curve(np.asarray(rho, np.float64), xp=np, base=base,
                           scale=scale, knee=knee)


def view_delay_params(view):
    """(base, scale, knee) per-node float64 arrays from a view, falling
    back to the homogeneous constants on views built without fleet
    fields (tests, benches, partial views)."""
    if getattr(view, "delay_base", None) is None:
        return sim.RUNQLAT_BASE, sim.RUNQLAT_SCALE, sim.RHO_EPS
    return (np.asarray(view.delay_base, np.float64),
            np.asarray(view.delay_scale, np.float64),
            np.asarray(view.rho_knee, np.float64))


def _node_delay_params(view, node: int):
    """One node's (base, scale, knee) as Python floats."""
    base, scale, knee = view_delay_params(view)
    if np.ndim(base) == 0:
        return float(base), float(scale), float(knee)
    return float(base[node]), float(scale[node]), float(knee[node])


class MitigationPolicy:
    """Plans (does not apply) mitigation actions for flagged hotspots."""

    def __init__(self, quantifier, config: PolicyConfig | None = None):
        self.q = quantifier
        self.cfg = config or PolicyConfig()

    # -------- helpers --------

    def _pressure(self, cluster, view, node: int, pods: list[dict]) -> float:
        """Burst-weighted run-queue pressure of a node (peak, not average)."""
        rho = float(view.cpu_cur[node] / view.cpu_sum[node])
        extra = sum(p["cores"] * (p["burst"] - 1.0) for p in pods
                    if p["kind"] == "off")
        return rho + extra / float(view.cpu_sum[node])

    def _relief(self, rho: float, dcores: float, cores: float,
                params=None) -> float:
        """Delay reduction from removing ``dcores`` of pressure at ``rho``;
        ``params`` is one node's (base, scale, knee) machine-class tuple."""
        b, s, k = params or (sim.RUNQLAT_BASE, sim.RUNQLAT_SCALE, sim.RHO_EPS)
        return float(node_delay_curve(rho, b, s, k)
                     - node_delay_curve(rho - dcores / cores, b, s, k))

    def _destinations(self, view, hot: np.ndarray, cpu_pod: float,
                      mem_pod: float, free_mask: np.ndarray) -> np.ndarray:
        """Feasible, non-hot destination nodes for a pod of given demand."""
        cfg = self.cfg
        cpu_ok = (view.cpu_cur + cfg.w_d * cpu_pod) / view.cpu_sum <= cfg.cpu_threshold
        mem_ok = (view.mem_cur + cfg.w_e * mem_pod) / view.mem_sum <= cfg.mem_threshold
        return np.nonzero(cpu_ok & mem_ok & ~hot & free_mask)[0]

    # -------- planning --------

    def plan(self, cluster, view, hot, exclude_uids=frozenset(),
             corrections=None, attribution=None, proactive=None,
             forecast_pressure=None, recorder=None,
             declined: dict | None = None) -> list[Action]:
        """view: the ``repro.cluster.ClusterView`` telemetry snapshot.
        exclude_uids: pods recently acted on (per-pod anti-ping-pong).
        corrections: per-kind multiplicative calibration of
            ``predicted_reduction`` learned by post-action verification
            (missing kinds default to 1.0, i.e. trust the cost model).
        attribution: (N, S) per-slot drift scores from the detector; when
            given, victims are the pods whose histograms drifted.
        proactive: optional (N,) bool mask of nodes flagged from *forecast*
            drift only — their candidates are costed at
            ``proactive_cost_scale`` and tagged ``proactive=True``.
        forecast_pressure: optional (N,) forecast run-queue pressure; relief
            on a proactive node is estimated at the pressure the forecast
            says it WILL carry (its current pressure is unremarkable by
            construction — the hotspot has not formed yet).
        recorder: optional ``repro.obs.TraceRecorder``; each chosen action
            gets an ``action_id`` and an ``ActionPlanned`` event recording
            the greedy ranking it won (correction applied, net gain, rank).
        declined: optional dict, filled with the guard that left each
            ``hot`` node without an action: ``no_candidate`` (no action to
            offer), ``net_gain`` (none buys more than it costs; with the
            ``best_net_gain``) or ``budget`` (the best one did not fit: the
            ``spent`` before it, its ``cost`` and the ``budget``).
        """
        hot = np.asarray(hot, bool)
        corrections = corrections or {}
        proactive = (np.zeros(hot.shape, bool) if proactive is None
                     else np.asarray(proactive, bool))
        candidates: list[Action] = []
        for node in np.nonzero(hot)[0]:
            node = int(node)
            rho_override = None
            if proactive[node] and forecast_pressure is not None:
                rho_override = float(forecast_pressure[node])
            candidates.extend(
                self._candidates(cluster, view, node, hot, exclude_uids,
                                 attribution, rho_override=rho_override,
                                 proactive=bool(proactive[node]))
            )

        def net_gain(a: Action) -> float:
            calibrated = corrections.get(a.kind, 1.0) * a.predicted_reduction
            return calibrated - self.cfg.cost_weight * a.cost

        offered = candidates
        candidates = [a for a in candidates if net_gain(a) > 0]
        candidates.sort(key=net_gain, reverse=True)
        chosen, spent, per_node = [], 0.0, {}
        used_uids: set[int] = set()
        over_budget: dict[int, dict] = {}
        for a in candidates:
            if spent + a.cost > self.cfg.budget:
                over_budget.setdefault(a.node, {"spent": spent,
                                                "cost": a.cost})
                continue
            if per_node.get(a.node, 0) >= self.cfg.max_actions_per_node:
                continue
            # one action per pod: migrate+scale-out of the same victim (or
            # evict+resize of the same job) conflict and double-count relief
            uid = getattr(a, "uid", -1)
            if uid in used_uids:
                continue
            chosen.append(a)
            spent += a.cost
            per_node[a.node] = per_node.get(a.node, 0) + 1
            used_uids.add(uid)
        if declined is not None:
            acted = {a.node for a in chosen}
            for node in np.nonzero(hot)[0]:
                node = int(node)
                gains = [net_gain(a) for a in offered if a.node == node]
                if node in acted:
                    continue
                if not gains:
                    declined[node] = {"guard": "no_candidate"}
                elif node in over_budget:
                    declined[node] = {"guard": "budget", **over_budget[node],
                                      "budget": self.cfg.budget}
                else:
                    declined[node] = {"guard": "net_gain",
                                      "best_net_gain": max(gains)}
        if recorder:
            from repro.obs import ActionPlanned
            for rank, a in enumerate(chosen):
                a.action_id = recorder.next_action_id()
                recorder.emit(ActionPlanned(
                    action=a.kind, action_id=a.action_id, node=a.node,
                    uid=getattr(a, "uid", -1), dst=getattr(a, "dst", -1),
                    cost=a.cost, predicted_reduction=a.predicted_reduction,
                    correction=corrections.get(a.kind, 1.0),
                    net_gain=net_gain(a), rank=rank, proactive=a.proactive,
                ))
        return chosen

    def _candidates(self, cluster, view, node: int, hot: np.ndarray,
                    exclude_uids=frozenset(), attribution=None,
                    rho_override=None, proactive=False) -> list[Action]:
        cfg = self.cfg
        pods = cluster.pods_on_node(node)
        eligible = [p for p in pods if p["uid"] not in exclude_uids]
        offline = [p for p in eligible if p["kind"] == "off"]
        online = [p for p in eligible if p["kind"] == "on"]
        cores = float(view.cpu_sum[node])
        node_params = _node_delay_params(view, node)  # machine-class curve
        rho_p = self._pressure(cluster, view, node, pods)  # all pods press
        if rho_override is not None:
            # proactive planning: relief priced at the forecast pressure —
            # never below the measured one (the forecast may lag reality)
            rho_p = max(rho_p, rho_override)
        out: list[Action] = []

        def drift(p: dict) -> float:
            """Per-slot drift score of a pod (0 without attribution).

            Online pods occupy detector slots [0, S_ON); offline pods are
            offset by S_ON, matching the hist_on ++ hist_off concatenation
            the ControlLoop feeds the detector.
            """
            if attribution is None:
                return 0.0
            s = p["slot"] + (0 if p["kind"] == "on" else sim.S_ON)
            return float(attribution[node, s])

        # offline offenders: the slot whose histogram drifted first, then
        # heaviest pressure source (cores x burst) as tie-break / fallback;
        # each contributes an evict and a throttle candidate so the greedy
        # pass can combine several cheap throttles or one decisive eviction
        offline.sort(key=lambda p: (drift(p), p["cores"] * p["burst"]),
                     reverse=True)
        for job in offline[:cfg.max_actions_per_node + 1]:
            dcores = job["cores"] * job["burst"]
            out.append(EvictOffline(
                node=node, uid=job["uid"],
                cost=cfg.evict_cost_per_core * job["cores"],
                predicted_reduction=self._relief(rho_p, dcores, cores,
                                                 node_params),
            ))
            new_cores = job["cores"] * cfg.throttle_frac
            if new_cores < cfg.min_offline_cores:
                continue  # already throttled to the floor: re-halving would
                          # shrink cores toward zero and stretch the job
                          # unboundedly for ever-smaller relief
            stretch = job["remaining"] * (1.0 / cfg.throttle_frac - 1.0)
            out.append(VerticalResize(
                node=node, uid=job["uid"],
                new_cores=new_cores,
                cost=cfg.resize_cost + 0.002 * stretch,
                predicted_reduction=self._relief(
                    rho_p, dcores * (1.0 - cfg.throttle_frac), cores,
                    node_params),
            ))

        if online and cfg.destination_actions:
            # the victim is the online pod whose own histogram drifted most
            # (the one actually suffering); QPS breaks ties / is the
            # fallback when no attribution is available
            victim = max(online, key=lambda p: (drift(p), p["qps"]))
            prof = ONLINE_PROFILES[victim["workload"]]
            cpu_pod = prof.cpu_per_qps * victim["qps"] + prof.cpu_base
            mem_pod = prof.mem_per_qps * victim["qps"] + prof.mem_base
            on_free = ~np.asarray(cluster.state.on_active).all(axis=1)
            # Eq.(3) prediction on every node at once: latency units
            pred = np.asarray(
                self.q.intf_pod(victim["qps"], view.features)
            ) * metric.OVERFLOW_EDGE
            dsts = self._destinations(view, hot, cpu_pod, mem_pod, on_free)
            if dsts.size:
                # topology-aware destination ranking: the bytes a migration
                # drags are the pod's memory footprint, priced as a multiple
                # of the same-rack transfer (1.0 on a flat topology, so the
                # homogeneous case ranks purely on predicted interference)
                factor = np.array([
                    view.migrate_cost_factor(node, int(d), mem_pod)
                    for d in dsts])
                eff = pred[dsts] + cfg.transfer_latency_weight * (factor - 1.0)
                j = int(np.argmin(eff))
                dst, dst_factor = int(dsts[j]), float(factor[j])
                # the pod rides along: only move it when the model predicts
                # a real gap, else migration is churn that stacks load on
                # whichever node happens to be in a seasonal trough.  No
                # explicit destination charge here (unlike scale-out below):
                # the RF maps PRE-placement node features to the runqlat the
                # pod REALIZED after landing, so pred[dst] already prices in
                # the pod's own added load on the destination
                if pred[node] - pred[dst] > cfg.migrate_margin:
                    out.append(MigrateOnline(
                        node=node, uid=victim["uid"], dst=dst,
                        cost=cfg.migrate_cost * dst_factor,
                        predicted_reduction=self._relief(rho_p, cpu_pod, cores,
                                                         node_params)
                        + (pred[node] - pred[dst]),
                    ))
                half = victim["qps"] / 2.0
                if half >= cfg.min_scale_qps:
                    # splitting QPS in half does NOT halve the pod's CPU:
                    # the source keeps its full cpu_base (relief is only
                    # the per-QPS share) and the replica brings a brand-new
                    # cpu_base to the destination — charge that added load
                    # against the destination's delay curve, else the
                    # estimate is systematically optimistic
                    cpu_half = prof.cpu_per_qps * half
                    dst_cores = float(view.cpu_sum[dst])
                    rho_dst = float(view.cpu_cur[dst] / dst_cores)
                    dst_add = cpu_half + prof.cpu_base
                    # the destination's own machine class prices the load
                    # the replica adds there
                    dst_penalty = self._relief(
                        rho_dst + dst_add / dst_cores, dst_add, dst_cores,
                        _node_delay_params(view, dst))
                    mem_half = prof.mem_per_qps * half + prof.mem_base
                    out.append(ScaleOut(
                        node=node, uid=victim["uid"], workload=victim["workload"],
                        dst=dst, replica_qps=half,
                        cost=cfg.scale_out_cost
                        * view.migrate_cost_factor(node, dst, mem_half),
                        predicted_reduction=self._relief(rho_p, cpu_half,
                                                         cores, node_params)
                        + 0.3 * max(pred[node] - pred[dst], 0.0)
                        - dst_penalty,
                    ))
        if proactive:
            for a in out:
                a.cost *= cfg.proactive_cost_scale
                a.proactive = True
        return out
