"""Simulated co-location cluster: nodes, workloads, traces, experiments.

The package's contract with every consumer is the **ClusterView layer**:
``Cluster.view()`` emits one typed ``ClusterView`` snapshot per telemetry
window — utilization and capacity arrays, Table-III features, per-slot
runqlat histograms, per-slot tenant uids, and (when a
``repro.control.ForecastService`` annotates it) the projected per-node
runqlat at horizon.  Schedulers (``repro.core``), the mitigation control
plane (``repro.control``), and the training-data generator all read the
same dataclass instead of re-interpreting an untyped dict, so a new
telemetry field is declared exactly once.
"""
from repro.cluster.fleet import (
    Fleet,
    MachineClass,
    Topology,
    MACHINE_CLASSES,
    make_fleet,
)
from repro.cluster.simulator import Cluster, ClusterState, NodeSpec, S_ON, S_OFF
from repro.cluster.state import FleetParams, batched_rollout
from repro.cluster.view import ClusterView
from repro.cluster.workloads import (
    Pod,
    ONLINE_PROFILES,
    OFFLINE_PROFILES,
    ONLINE_NAMES,
    OFFLINE_NAMES,
)

__all__ = [
    "Cluster",
    "ClusterState",
    "ClusterView",
    "Fleet",
    "FleetParams",
    "MachineClass",
    "Topology",
    "MACHINE_CLASSES",
    "NodeSpec",
    "make_fleet",
    "batched_rollout",
    "S_ON",
    "S_OFF",
    "Pod",
    "ONLINE_PROFILES",
    "OFFLINE_PROFILES",
    "ONLINE_NAMES",
    "OFFLINE_NAMES",
]
