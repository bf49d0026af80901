"""ClusterView — the typed Data Collection Module snapshot (paper Sec. IV-A).

One telemetry window of the whole cluster as a dataclass of arrays, built by
``Cluster.view()`` and consumed by every scheduler (``repro.core.scheduler``
/ ``repro.core.baselines``), the mitigation control plane
(``repro.control.loop`` / ``repro.control.policy``), and the training-data
generator (``repro.cluster.dataset``).  It replaces the untyped
``nodes_data`` dict those layers used to re-interpret independently: a
telemetry field is now declared once, named once, and available to every
consumer — adding one is a one-place change here plus the builder in
``Cluster.view()``.

The view also carries the *forecast* fields (``forecast_runqlat`` /
``forecast_rho`` / ``forecast_trusted``), filled in by
``repro.control.forecast.ForecastService.annotate``: the per-node runqlat
the shared seasonal projection expects ``horizon`` telemetry windows ahead.
They default to ``None`` — a view without an attached forecast service is
simply a present-time snapshot, and forecast-aware consumers (the ICO-F
scheduler) degrade exactly to their present-time behaviour.

Views are built host-side from the ``ClusterState`` pytree
(``repro.cluster.state``): the batched/scanned rollout core never
materialises a ClusterView — it carries the raw arrays — and the shell
converts to this dataclass only at scheduler/control-plane decision points.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import metric


@dataclasses.dataclass
class ClusterView:
    """Snapshot of one telemetry window across all nodes.

    Array shapes use N = nodes, S_ON/S_OFF = online/offline slots per node,
    S = S_ON + S_OFF (detector layout: online slots first), B = 200 runqlat
    histogram bins, F = Table-III feature columns.  Partial views (fields
    left ``None``) are legal for consumers that only read a subset — tests
    and benchmarks construct them directly.
    """

    t: float = 0.0                                # cluster clock (ticks)
    window_ticks: int | None = None               # ticks the window spans,
                                                  # ending at t
    cpu_cur: np.ndarray | None = None             # (N,) window-mean CPU demand
    cpu_sum: np.ndarray | None = None             # (N,) node CPU capacity
    mem_cur: np.ndarray | None = None             # (N,) window-mean MEM used
    mem_sum: np.ndarray | None = None             # (N,) node MEM capacity
    online_hists: np.ndarray | None = None        # (N, S_ON, B) runqlat hists
    offline_hists: np.ndarray | None = None       # (N, S_OFF, B)
    slot_hists: np.ndarray | None = None          # (N, S, B) detector layout
    features: np.ndarray | None = None            # (N, F) Table-III features
    online_qps: np.ndarray | None = None          # (N, S_ON) window-mean QPS
    online_qps_sum: np.ndarray | None = None      # (N,) active-slot QPS total
    on_active: np.ndarray | None = None           # (N, S_ON) bool
    on_type: np.ndarray | None = None             # (N, S_ON) workload type id
    off_pressure: np.ndarray | None = None        # (N,) burst-weighted cores
    cpu_util: np.ndarray | None = None            # (N,) window-mean CPU util
    mem_util: np.ndarray | None = None            # (N,) window-mean MEM util
    slot_uids: np.ndarray | None = None           # (N, S) tenant uid, -1 vacant
    # --- filled by ForecastService.annotate (None = channel closed) ---
    forecast_runqlat: np.ndarray | None = None    # (N,) projected avg runqlat
    forecast_rho: np.ndarray | None = None        # (N,) projected pressure,
                                                  #      clamped at rho_cap
    forecast_trusted: np.ndarray | None = None    # (N,) >=1 pod passed the gate
    # --- fleet / topology (None = homogeneous single-rack fleet) ---
    node_class: tuple[str, ...] | None = None     # (N,) machine-class names
    fleet: object | None = None                   # repro.cluster.fleet.Fleet
    delay_base: np.ndarray | None = None          # (N,) float64 curve base
    delay_scale: np.ndarray | None = None         # (N,) float64 curve scale
    rho_knee: np.ndarray | None = None            # (N,) float64 curve knee

    _node_runqlat_avg: np.ndarray | None = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    @property
    def num_nodes(self) -> int:
        return len(self.cpu_sum)

    def node_runqlat_avg(self) -> np.ndarray:
        """(N,) average runqlat of this window's node histograms (cached)."""
        if self._node_runqlat_avg is None:
            hists = self.slot_hists
            if hists is None:
                hists = np.concatenate(
                    [self.online_hists, self.offline_hists], axis=1)
            self._node_runqlat_avg = np.asarray(
                metric.avg_runqlat(np.asarray(hists).sum(1)))
        return self._node_runqlat_avg

    def take(self, idx) -> "ClusterView":
        """A candidate sub-view: per-node leading axes sliced to ``idx``.

        The top-k admission pass scores only candidate nodes, so the
        expensive interference terms run on k rows instead of N.  The
        ``fleet`` handle is dropped (its node indices would dangle on a
        sliced view); ``node_class`` and the delay params are re-indexed.
        """
        idx = np.asarray(idx)

        def take(a):
            return None if a is None else np.asarray(a)[idx]

        return dataclasses.replace(
            self,
            cpu_cur=take(self.cpu_cur), cpu_sum=take(self.cpu_sum),
            mem_cur=take(self.mem_cur), mem_sum=take(self.mem_sum),
            online_hists=take(self.online_hists),
            offline_hists=take(self.offline_hists),
            slot_hists=take(self.slot_hists), features=take(self.features),
            online_qps=take(self.online_qps),
            online_qps_sum=take(self.online_qps_sum),
            on_active=take(self.on_active), on_type=take(self.on_type),
            off_pressure=take(self.off_pressure),
            cpu_util=take(self.cpu_util), mem_util=take(self.mem_util),
            slot_uids=take(self.slot_uids),
            forecast_runqlat=take(self.forecast_runqlat),
            forecast_rho=take(self.forecast_rho),
            forecast_trusted=take(self.forecast_trusted),
            node_class=(None if self.node_class is None
                        else tuple(self.node_class[i] for i in idx)),
            fleet=None,
            delay_base=take(self.delay_base),
            delay_scale=take(self.delay_scale),
            rho_knee=take(self.rho_knee),
        )

    def zone_of(self, node: int) -> int:
        """Availability zone of a node (0 on a topology-less view)."""
        if self.fleet is None:
            return 0
        return self.fleet.topology.zone_of(node)

    def transfer_cost(self, src: int, dst: int, gb: float) -> float:
        """Seconds to move ``gb`` GB src -> dst over the bottleneck link.

        A topology-less view prices every pair at the same-rack rate, so
        consumers need not special-case homogeneous clusters."""
        if self.fleet is None:
            from repro.cluster.fleet import Topology
            return Topology.flat(self.num_nodes).transfer_cost(src, dst, gb)
        return self.fleet.topology.transfer_cost(src, dst, gb)

    def migrate_cost_factor(self, src: int, dst: int, gb: float) -> float:
        """Transfer cost relative to the same-rack price (1.0 without a
        topology — the degenerate case reprices nothing)."""
        if self.fleet is None:
            return 1.0
        return self.fleet.topology.cost_factor(src, dst, gb)

    def forecast_drift(self) -> np.ndarray | None:
        """(N,) projected runqlat *increase* at horizon, in latency units.

        ``None`` while the forecast channel is closed (no service attached,
        or the forecaster has not observed its cadence yet); zero on nodes
        with no trusted pod — so forecast-aware scoring degrades exactly to
        present-time scoring whenever the trust gate is shut.
        """
        if self.forecast_runqlat is None:
            return None
        drift = np.maximum(
            np.asarray(self.forecast_runqlat) - self.node_runqlat_avg(), 0.0)
        if self.forecast_trusted is not None:
            drift = np.where(np.asarray(self.forecast_trusted, bool), drift, 0.0)
        return drift
