"""Discrete-time co-location cluster simulator (JAX-vectorized).

Faithful to the paper's testbed: nodes with 32 cores / 64 GB RAM running a
mix of online services (QPS-driven) and offline batch jobs.  Each 30s tick
computes, for every node in one jit'd call:

  * per-pod CPU demand (online: linear in instantaneous QPS; offline: the
    allocated cores),
  * run-queue pressure rho -> per-pod scheduling-latency (runqlat) samples
    drawn from a gamma distribution whose mean follows an M/G/1-PS-style
    delay curve (convex in rho, unbounded near saturation),
  * online response times: RT = f(service) + rt_per_runqlat * runqlat
    (queueing delay is the causal path — CPU utilization saturates at 1.0
    and loses information, which is exactly the paper's motivation),
  * Table-III telemetry: perf metrics, hardware events, runqlat histograms.

The simulation core lives in ``repro.cluster.state``: an immutable
``ClusterState`` pytree, pure place/migrate/evict/resize/reconcile array
transforms, and the tick/window scan kernels.  ``Cluster`` here is the thin
stateful shell the drivers talk to — it owns the host-side bookkeeping
(pod-uid map, numpy RNG for phases/bursts, the JAX key), delegates every
mutation to the pure transforms, and **logs each mutation as a replayable
event** so an entire run's placement/mitigation schedule can be replayed
inside the scanned core (``state.batched_rollout``) under fresh simulation
seeds.

``rollout(n)`` advances the cluster: every 10-tick chunk of the window is
scanned in ONE jit dispatch (``state.rollout_chunks``), each chunk keyed by
an iterative split of the cluster's key (``state.chunk_key_stream``), and
the per-chunk summaries are merged host-side (``state.merge_summaries``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster import workloads as W
from repro.cluster import state as cstate
from repro.cluster.fleet import Fleet
from repro.cluster.state import (  # re-exported: the historical home
    CHUNK,
    GAMMA_SHAPE,
    OS_BASE_CORES,
    RHO_EPS,
    RUNQLAT_BASE,
    RUNQLAT_SCALE,
    S_OFF,
    S_ON,
    SAMPLES_PER_TICK,
    TICKS_PER_DAY,
    ClusterState,
    _season,
    delay_curve,
)
from repro.cluster.workloads import Pod

__all__ = [
    "Cluster", "ClusterState", "Fleet", "NodeSpec", "S_ON", "S_OFF",
    "SAMPLES_PER_TICK", "TICKS_PER_DAY", "OS_BASE_CORES", "RUNQLAT_BASE",
    "RUNQLAT_SCALE", "RHO_EPS", "GAMMA_SHAPE", "delay_curve",
]


@dataclasses.dataclass(frozen=True)
class NodeSpec:
    """Per-node capacity. Frozen: Cluster.__init__ historically used a
    shared ``NodeSpec()`` default instance, so a caller mutating one
    cluster's spec would silently retune every later cluster."""
    cores: float = 32.0
    mem_gb: float = 64.0


class Cluster:
    """Host-side cluster manager: a thin stateful shell over ClusterState."""

    CHUNK = CHUNK  # fixed scan length -> exactly one XLA compilation

    def __init__(self, num_nodes: int = 12, spec: NodeSpec | None = None,
                 seed: int = 0, fleet: Fleet | None = None):
        if fleet is not None:
            # the fleet is authoritative: per-node capacities come from
            # its machine classes, so a scalar NodeSpec cannot also apply
            if spec is not None:
                raise ValueError(
                    "pass capacities via the fleet's machine classes, "
                    "not a NodeSpec")
            num_nodes = fleet.num_nodes
            self.spec = None
            self.state = ClusterState.create(
                num_nodes, fleet.cores(), fleet.mem_gb())
        else:
            # legacy homogeneous path: kept verbatim (scalar create call)
            # so pre-fleet clusters stay bitwise-identical
            spec = NodeSpec() if spec is None else spec
            self.spec = spec
            self.state = ClusterState.create(num_nodes, spec.cores,
                                             spec.mem_gb)
        self.n = num_nodes
        self.fleet = fleet
        self.fleet_params = (fleet.params() if fleet is not None
                             else cstate.FleetParams.uniform(num_nodes))
        self.rng = np.random.default_rng(seed)
        self.key = jax.random.PRNGKey(seed)
        self.t = 0.0
        self.profiles = {k: jnp.asarray(v) for k, v in W.online_arrays().items()}
        self.last: dict | None = None
        self._pod_slots: dict[int, tuple[str, int, int]] = {}  # uid -> (kind, node, slot)
        self._uid = 0
        # replayable mutation events: (op, t, node, slot, *params) host
        # tuples consumed by state.extract_plan for batched replay
        self.log: list[tuple] = []

    # ---------------- placement ----------------

    def place(self, pod: Pod, node: int) -> bool:
        """Place a pod on a node. Returns False if the node has no free slot."""
        if node < 0 or node >= self.n:
            return False
        if pod.is_online:
            free = np.nonzero(~np.asarray(self.state.on_active[node]))[0]
            if free.size == 0:
                return False
            s = int(free[0])
            prof = W.ONLINE_PROFILES[pod.workload]
            phase = float(self.rng.uniform(0, 2 * np.pi))
            self.state = cstate.place_online(
                self.state, node, s, prof.type_id, float(pod.qps), phase)
            self.log.append(("place_on", self.t, node, s,
                             prof.type_id, float(pod.qps), phase))
            kind = "on"
        else:
            free = np.nonzero(~np.asarray(self.state.off_active[node]))[0]
            if free.size == 0:
                return False
            s = int(free[0])
            prof = W.OFFLINE_PROFILES[pod.workload]
            cores = float(pod.cpu_demand)
            threads = float(cores * prof.threads_per_core)
            mem = float(cores * prof.mem_per_core)
            burst = float(self.rng.uniform(*prof.burst_range))
            remaining = int(pod.duration)
            self.state = cstate.place_offline(
                self.state, node, s, cores, threads, mem, burst, remaining)
            self.log.append(("place_off", self.t, node, s,
                             cores, threads, mem, burst, remaining))
            kind = "off"
        pod.uid = self._uid
        self._pod_slots[pod.uid] = (kind, node, s)
        self._uid += 1
        return True

    def remove(self, uid: int) -> None:
        # reconcile first so a kernel-expired offline uid raises the same
        # KeyError as migrate()/resize() instead of double-evicting a slot
        # the kernel already deactivated
        self.reconcile()
        if uid not in self._pod_slots:
            raise KeyError(
                f"unknown pod uid {uid}: never placed, already removed, or a "
                f"finished offline job cleared by reconcile()"
            )
        kind, node, s = self._pod_slots.pop(uid)
        # both evict transforms clear the slot's parameters, so readers of
        # raw state between this remove and the next reconcile never see
        # the ghost allocation of the departed pod
        if kind == "on":
            self.state = cstate.evict_online(self.state, node, s)
        else:
            self.state = cstate.evict_offline(self.state, node, s)
        self.log.append((f"evict_{kind}", self.t, node, s))

    def reconcile(self) -> list[int]:
        """Clear offline jobs whose run finished (off_remaining hit 0).

        The rollout kernel deactivates finished slots but cannot touch the
        host-side ``_pod_slots`` map, so without this the map leaks and stale
        off_cores/off_mem persist in state (invisible to the sim, which masks
        by off_active, but wrong for any code reading raw state — which is
        why ``remove()`` reconciles first and the evict transforms clear
        slot params at remove time rather than waiting for this sweep).
        Returns the uids of the jobs that were cleared.  Not logged: the replay path
        needs no reconcile events, because its dynamics mask by off_active
        and placements overwrite every slot field.
        """
        off_active = np.asarray(self.state.off_active)
        finished = [
            uid for uid, (kind, node, s) in self._pod_slots.items()
            if kind == "off" and not off_active[node, s]
        ]
        for uid in finished:
            self._pod_slots.pop(uid)
        if finished:
            self.state, _ = cstate.reconcile(self.state)
        return finished

    # ---------------- runtime mitigation primitives ----------------

    def migrate(self, uid: int, dst: int) -> bool:
        """Move a live pod to another node, preserving its parameters.

        Returns False when the destination has no free slot of the right
        kind (state is untouched); raises KeyError for unknown uids.
        """
        self.reconcile()
        if uid not in self._pod_slots:
            raise KeyError(f"cannot migrate unknown pod uid {uid}")
        kind, src, s = self._pod_slots[uid]
        if dst < 0 or dst >= self.n:
            return False
        if dst == src:
            return True
        active = np.asarray(getattr(self.state, f"{kind}_active")[dst])
        free = np.nonzero(~active)[0]
        if free.size == 0:
            return False
        d = int(free[0])
        mover = cstate.migrate_online if kind == "on" else cstate.migrate_offline
        self.state = mover(self.state, src, s, dst, d)
        self.log.append((f"migrate_{kind}", self.t, src, s, dst, d))
        self._pod_slots[uid] = (kind, dst, d)
        return True

    def resize(self, uid: int, *, cores: float | None = None,
               qps: float | None = None) -> bool:
        """Vertically resize a live pod in place.

        Offline (``cores``): rescales cores/threads/mem by the per-core
        ratios currently in state and stretches off_remaining by the inverse
        ratio so total work is conserved (throttling trades latency of the
        batch job for run-queue relief).  Online (``qps``): retargets the
        mean QPS, the knob horizontal scale-out splits across replicas.
        """
        self.reconcile()
        if uid not in self._pod_slots:
            raise KeyError(f"cannot resize unknown pod uid {uid}")
        kind, node, s = self._pod_slots[uid]
        if kind == "off":
            if cores is None or cores <= 0:
                return False
            old = float(self.state.off_cores[node, s])
            if old <= 0:
                return False
            ratio = cores / old
            new_threads = float(self.state.off_threads[node, s]) * ratio
            new_mem = float(self.state.off_mem[node, s]) * ratio
            rem = int(self.state.off_remaining[node, s])
            new_rem = max(int(round(rem / ratio)), 1)
            self.state = cstate.resize_offline(
                self.state, node, s, old * ratio, new_threads, new_mem,
                new_rem)
            self.log.append(("resize_off", self.t, node, s,
                             old * ratio, new_threads, new_mem, 0.0, new_rem))
        else:
            if qps is None or qps < 0:
                return False
            self.state = cstate.resize_online(self.state, node, s, float(qps))
            self.log.append(("resize_on", self.t, node, s, float(qps)))
        return True

    def pods_on_node(self, node: int) -> list[dict]:
        """Host-side inventory of live pods on a node (for mitigation policies)."""
        self.reconcile()
        out = []
        for uid, (kind, n_, s) in self._pod_slots.items():
            if n_ != node:
                continue
            if kind == "on":
                type_id = int(self.state.on_type[node, s])
                out.append({
                    "uid": uid, "kind": "on", "slot": s,
                    "workload": W.ONLINE_BY_TYPE[type_id],
                    "qps": float(self.state.on_qps_mean[node, s]),
                })
            else:
                out.append({
                    "uid": uid, "kind": "off", "slot": s,
                    "cores": float(self.state.off_cores[node, s]),
                    "burst": float(self.state.off_burst[node, s]),
                    "remaining": int(self.state.off_remaining[node, s]),
                })
        return out

    def active_pod_count(self) -> int:
        """Number of active slots across the cluster (invariant checks)."""
        return int(np.asarray(self.state.on_active).sum()
                   + np.asarray(self.state.off_active).sum())

    def slot_uids(self) -> np.ndarray:
        """(N, S_ON + S_OFF) tenant uid per slot, -1 when vacant.

        Detector layout (online slots first, offline offset by S_ON): the
        control plane diffs consecutive snapshots to notice slot reuse —
        place / migrate / evict all change the tenant — and resets its
        per-slot attribution and forecast state for exactly those slots.
        """
        self.reconcile()
        uids = np.full((self.n, S_ON + S_OFF), -1, np.int64)
        for uid, (kind, node, s) in self._pod_slots.items():
            uids[node, s if kind == "on" else S_ON + s] = uid
        return uids

    # ---------------- simulation ----------------

    def rollout(self, num_ticks: int) -> dict:
        """Advance ~num_ticks ticks (rounded up to CHUNK multiples) in one
        jit dispatch; returns the window's merged telemetry (``self.last``).

        Chunk ``i`` is keyed by the ``i``-th iterative split of
        ``self.key``, and the stacked per-chunk summaries are merged
        host-side, so the result is bitwise an eager loop of per-chunk
        ``_window_core`` calls (the oracle in ``tests/test_state.py``).
        """
        chunks = max(1, -(-num_ticks // self.CHUNK))
        self.key, ks = cstate.chunk_key_stream(self.key, chunks)
        self.state, stacked = cstate.rollout_chunks(
            self.state, self.profiles, self.fleet_params,
            jnp.float32(self.t), ks)
        self.t += chunks * self.CHUNK
        stacked = jax.tree.map(np.asarray, stacked)
        parts = [jax.tree.map(lambda a, i=i: a[i], stacked)
                 for i in range(chunks)]
        self.last = cstate.merge_summaries(parts)
        self.reconcile()
        return self.last

    # chipbench's live cell calls the rollout by this name
    rollout_scan = rollout

    # ---------------- Data Collection Module ----------------

    def view(self) -> "ClusterView":
        """Typed collector snapshot consumed by every scheduler and the
        control plane (paper Sec. IV-A) — built straight from the
        ``ClusterState`` pytree + the last window's telemetry; see
        ``repro.cluster.view``."""
        if self.last is None:
            self.rollout(30)
        from repro.core.predictors.features import runqlat_summary
        from repro.cluster.view import ClusterView

        s = self.last
        node_hist = s["hist_on"].sum(1) + s["hist_off"].sum(1)  # (N, 200)
        summaries = runqlat_summary(node_hist)           # (N, 25)
        features = np.concatenate([s["perf"], s["hw"], summaries], axis=1)
        on_active = np.asarray(self.state.on_active)
        # per-slot histograms in detector layout: online slots [0, S_ON),
        # offline slots [S_ON, S_ON + S_OFF) — per-pod attribution keys on it
        slot_hists = np.concatenate([s["hist_on"], s["hist_off"]], axis=1)
        off_active = np.asarray(self.state.off_active)
        off_pressure = (np.asarray(self.state.off_cores)
                        * np.asarray(self.state.off_burst)
                        * off_active).sum(-1)
        # per-node delay-curve params in float64, derived from the machine
        # classes' Python floats (never widened from the f32 kernel arrays)
        # so host-side relief math keeps its historical double precision
        if self.fleet is not None:
            d64 = self.fleet.delay_params64()
            node_class = self.fleet.class_names()
        else:
            d64 = {"base": np.full(self.n, RUNQLAT_BASE, np.float64),
                   "scale": np.full(self.n, RUNQLAT_SCALE, np.float64),
                   "knee": np.full(self.n, RHO_EPS, np.float64)}
            node_class = None
        return ClusterView(
            t=float(self.t),
            window_ticks=int(s["rt"].shape[0]),
            cpu_cur=s["cpu_demand"],
            cpu_sum=np.asarray(self.state.cpu_sum),
            mem_cur=s["mem_used"],
            mem_sum=np.asarray(self.state.mem_sum),
            online_hists=s["hist_on"],
            offline_hists=s["hist_off"],
            slot_hists=slot_hists,
            features=features,
            online_qps=s["qps"],             # (N, S_ON) window-mean per slot
            online_qps_sum=(s["qps"] * on_active).sum(-1),
            on_active=on_active,
            on_type=np.asarray(self.state.on_type),
            off_pressure=off_pressure,       # burst-weighted offline cores
            cpu_util=s["cpu_util"],
            mem_util=s["mem_util"],
            slot_uids=self.slot_uids(),
            node_class=node_class,
            fleet=self.fleet,
            delay_base=d64["base"],
            delay_scale=d64["scale"],
            rho_knee=d64["knee"],
        )

    def online_rt_samples(self) -> np.ndarray:
        """Flat response-time samples of all active online pods, last window."""
        s = self.last
        active = np.asarray(self.state.on_active)  # (N, S_ON)
        rt = s["rt"]  # (W, N, S_ON)
        mask = np.broadcast_to(active, rt.shape)
        return rt[mask & (rt > 0)]
