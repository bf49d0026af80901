"""Closed-loop controller: detect hotspots, plan mitigations, act, verify.

``ControlLoop.step(cluster, view=None)`` consumes the typed
``repro.cluster.ClusterView`` snapshot for the last telemetry window
(building one from the cluster when the driver does not pass it in), feeds
the per-slot runqlat histograms to the streaming detector (one jit'd call
over all nodes and slots), and — every ``interval``-th invocation with at
least one flagged node — asks the mitigation policy for a budgeted action
plan and applies it.

The loop is *verified*, not open-loop: every applied action records the
source node's raw-window average runqlat, and on the next ``step`` the
observed delta is compared against the action's ``predicted_reduction``.
An online per-action-kind multiplicative correction (EWMA of the
realized/predicted ratio, clipped) rescales future predictions in the
policy's greedy ranking, so action kinds that over-promise are demoted and
the cost model self-calibrates during the run.  Realized-vs-predicted
totals are surfaced in ``ControlStats`` and per-step ``history`` entries.
A post-action window is only trusted when the node's pod *signature* — the
uid set AND each pod's QPS/cores parameters — is unchanged: uid diffs
catch arrivals and departures, the parameter check catches QPS
renormalisation (a scale-out halves the source pod's QPS without touching
the uid set), either of which would make the delta measure the churn
rather than the action.

The loop is optionally *proactive*: with ``proactive=True`` every step
feeds the view to a ``repro.control.forecast.ForecastService`` — an
internally-owned one by default, or a caller-supplied *shared* instance so
the admission path (``ICOFScheduler``) and the mitigation loop price
contention with the same projection, trust gate, and ``rho_cap`` clamp.
The service projects node runqlat ``horizon`` windows ahead through the
delay-curve model and the detector's forecast-CUSUM channel turns the
projection into ``proactive=True`` flags: the policy prices their relief
at the *forecast* pressure and discounts their cost (the pod moves before
its worst window), and they are exempt from post-action verification — the
window they mitigate has not happened yet, so next window's delta would
read as a spurious miss and poison the per-kind corrections.

``run(cluster, num_ticks, k)`` interleaves the loop with
``Cluster.rollout`` every ``k`` ticks for standalone use; experiment
drivers that own the rollout cadence (``run_experiment``) just call
``step`` at their own tick boundaries.

``scheduler_loop_config`` maps a scheduler name to a tuned
``ControlLoopConfig``: the default profile was tuned against ICO
placements, and replaying PR 2's grid showed it can *hurt* RR/HUP — their
placements leave different headroom patterns, so those schedulers get a
conservative profile (wider margins, longer cooldowns, smaller budget)
under which mitigation is non-harmful on the regressing seeds.
"""
from __future__ import annotations

import dataclasses
import weakref
from collections import deque

import jax
import numpy as np

from repro.control.actions import Action
from repro.control.detector import DetectorConfig, StreamingDetector
from repro.control.forecast import ForecastConfig, ForecastService
from repro.control.policy import MitigationPolicy, PolicyConfig
from repro.obs import (
    ActionExecuted,
    ActionVerified,
    HotspotFlag,
    MetricsRegistry,
    PhaseTimers,
    PhaseTimings,
)


@dataclasses.dataclass(frozen=True)
class ControlLoopConfig:
    interval: int = 1      # act on every interval-th step() call
    cooldown: int = 2      # steps a node is left alone after being acted on
    uid_cooldown: int = 4  # steps a pod is left alone after being acted on
    corr_beta: float = 0.35  # EWMA rate of the per-kind calibration factor
    corr_min: float = 0.4    # calibration clamp: demote an over-promising kind
                             # at most 2.5x — post-action windows are noisy
                             # (seasonal QPS drift, rollout jitter), and an
                             # unlucky sample must not bury a kind for good
    corr_max: float = 2.0    # ... nor credit it more than 2x its prediction
    proactive: bool = False  # forecast channel + ahead-of-time mitigation
    horizon: float = 6.0     # how many telemetry windows ahead to project:
                             # long enough for real diurnal movement (~30 deg
                             # of phase at the bench cadence), short enough
                             # that the acted-on window arrives within a few
                             # cooldown periods
    history_limit: int = 512  # ring-buffer bound on ControlLoop.history —
                              # week-long traces flag thousands of windows
                              # and the full record belongs in the trace
                              # artifact, not in resident memory
    detector: DetectorConfig = dataclasses.field(default_factory=DetectorConfig)
    policy: PolicyConfig = dataclasses.field(default_factory=PolicyConfig)
    forecast: ForecastConfig = dataclasses.field(default_factory=ForecastConfig)


@dataclasses.dataclass
class ControlStats:
    """Backward-compatible snapshot view over the loop's metrics registry.

    The counters themselves now live in ``ControlLoop.metrics`` (a
    ``repro.obs.MetricsRegistry``); ``ControlLoop.stats`` assembles one of
    these on each access, so every existing reader — benches, tests,
    examples — keeps working unchanged.
    """

    steps: int = 0
    hotspots_flagged: int = 0
    proactive_flagged: int = 0   # forecast-channel flags (predicted drift)
    actions_planned: int = 0
    actions_applied: int = 0
    proactive_applied: int = 0   # subset of applied planned ahead of time
    actions_verified: int = 0
    verifications_discarded: int = 0  # post-action windows too churned to read
    predicted_reduction: float = 0.0  # sum of predictions of verified actions
    realized_reduction: float = 0.0   # sum of observed post-action deltas
    calibration_abs_error: float = 0.0  # sum |realized - predicted|
    by_kind: dict = dataclasses.field(default_factory=dict)

    def calibration_error(self) -> float:
        """Mean relative |realized - predicted| error of the cost model."""
        return self.calibration_abs_error / max(self.predicted_reduction, 1e-9)

    @property
    def mean_calibration_abs_error(self) -> float:
        """Mean |realized - predicted| per verified action (latency units).

        The one canonical denominator: benches used to re-derive this from
        ``calibration_abs_error`` with subtly different divisors (verified
        count here, predicted sum there).  0.0 with nothing verified.
        """
        return self.calibration_abs_error / max(self.actions_verified, 1)


class ControlLoop:
    """Runtime interference-mitigation controller for one cluster."""

    def __init__(self, quantifier, config: ControlLoopConfig | None = None,
                 forecast_service: ForecastService | None = None,
                 recorder=None):
        self.cfg = config or ControlLoopConfig()
        self.policy = MitigationPolicy(quantifier, self.cfg.policy)
        # counters live here; `loop.stats` assembles the ControlStats view
        self.metrics = MetricsRegistry()
        self.timers = PhaseTimers("repro.loop", jax.profiler.TraceAnnotation)
        self.history: deque[dict] = deque(maxlen=self.cfg.history_limit)
        # per-kind multiplicative calibration of predicted_reduction,
        # learned online from post-action verification (1.0 = trust model)
        self.corrections: dict[str, float] = {}
        # a caller-supplied service is SHARED (e.g. with the ICO-F admission
        # path) and survives reset(): its lifetime — including warm starts
        # across runs — belongs to the owner, not to this loop.  Its OWN
        # ForecastConfig/horizon govern the projection (that is the point of
        # sharing: one gate for admission and mitigation), so build it from
        # this loop's profile — ForecastService(cfg.forecast, cfg.horizon) —
        # when the loop's forecast knobs are tuned, or cfg.forecast/
        # cfg.horizon are silently unused
        self._external_forecast = forecast_service
        self._recorder = recorder
        # opt-in outcome records: set to a list and every step appends its
        # window's flags and, per flagged node, the actions taken or the
        # guard that declined them (``_record_outcomes``)
        self.outcomes: list[dict] | None = None
        self.reset()

    @property
    def stats(self) -> ControlStats:
        """Snapshot of the metrics registry as the legacy ControlStats."""
        v = self.metrics.value
        return ControlStats(
            steps=int(v("steps")),
            hotspots_flagged=int(v("hotspots_flagged")),
            proactive_flagged=int(v("proactive_flagged")),
            actions_planned=int(v("actions_planned")),
            actions_applied=int(v("actions_applied")),
            proactive_applied=int(v("proactive_applied")),
            actions_verified=int(v("actions_verified")),
            verifications_discarded=int(v("verifications_discarded")),
            predicted_reduction=v("predicted_reduction"),
            realized_reduction=v("realized_reduction"),
            calibration_abs_error=v("calibration_abs_error"),
            by_kind={name[len("applied_kind."):]: int(c) for name, c
                     in self.metrics.counters("applied_kind.").items()},
        )

    @property
    def recorder(self):
        return self._recorder

    @recorder.setter
    def recorder(self, rec) -> None:
        self._recorder = rec
        # an internally-owned forecast service traces into the same sink;
        # an external (shared) one belongs to its owner, who wires it
        if self._external_forecast is None and self.forecast_service is not None:
            self.forecast_service.recorder = rec

    def reset(self) -> None:
        """Forget per-cluster state: detector, cooldowns, pending checks.

        Called automatically when ``step`` sees a new cluster object (even
        one of the same size — node/pod ids and telemetry baselines from
        another cluster are stale).  Learned ``corrections`` and cumulative
        ``stats``/``history`` survive: calibration is a property of the
        cost model, not of one cluster, and drivers that reuse a loop
        report per-run deltas (see ``run_experiment``).  An internally-owned
        forecast service is rebuilt; an external one is left to its owner.
        """
        self.detector: StreamingDetector | None = None
        if self._external_forecast is not None:
            self.forecast_service: ForecastService | None = \
                self._external_forecast
        else:
            self.forecast_service = (
                ForecastService(self.cfg.forecast, self.cfg.horizon)
                if self.cfg.proactive else None)
            if self.forecast_service is not None:
                self.forecast_service.recorder = self._recorder
        self._cluster_ref = lambda: None
        self._last_acted: dict[int, int] = {}      # node -> step of last action
        self._uid_last_acted: dict[int, int] = {}  # pod uid -> step (anti-ping-pong)
        self._pending: dict[int, int] = {}         # hot node -> step flagged
        self._pending_pro: dict[int, int] = {}     # forecast-flagged, disjoint
        self._to_verify: list[Action] = []         # applied last step, unchecked
        self._verify_sig: dict[int, frozenset] = {}  # node -> pod signature
        self._slot_uids: np.ndarray | None = None  # last (N, S) tenant snapshot

    @property
    def forecaster(self):
        """The shared service's per-pod fits (None while the channel is off)."""
        svc = self.forecast_service
        return svc.forecaster if svc is not None else None

    @staticmethod
    def _node_signature(cluster, node: int) -> frozenset:
        """Pod set AND per-pod load parameters of a node, for verification.

        uid diffs catch arrivals/departures; the QPS/cores parameters catch
        renormalisation — a scale-out halves the source pod's QPS without
        changing the uid set, and a window after such a change measures the
        renormalisation, not the verified action.
        """
        return frozenset(
            (p["uid"], round(float(p.get("qps", p.get("cores", 0.0))), 6))
            for p in cluster.pods_on_node(node)
        )

    def _verify(self, cluster, window_avg: np.ndarray) -> list[dict]:
        """Compare last step's actions against the runqlat actually observed.

        The node's realized delta is attributed across same-node actions
        proportionally to their predictions (they share one telemetry
        window), and each action's kind correction moves toward its clipped
        realized/predicted ratio.  A node whose pod signature changed
        between acting and checking (a new arrival landed, a batch job
        finished, a pod's QPS was renormalised) is discarded: its delta
        measures the churn, not the action, and one contaminated sample can
        drag a kind's correction to the floor.
        """
        verified: list[dict] = []
        if not self._to_verify:
            return verified
        cfg = self.cfg
        m = self.metrics
        rec = self._recorder
        by_node: dict[int, list[Action]] = {}
        for a in self._to_verify:
            by_node.setdefault(a.node, []).append(a)
        for node, acts in by_node.items():
            now = self._node_signature(cluster, node)
            if now != self._verify_sig.get(node):
                m.inc("verifications_discarded", len(acts))
                if rec:
                    for a in acts:
                        rec.emit(ActionVerified(
                            action=a.kind, action_id=a.action_id, node=node,
                            outcome="discarded",
                            predicted=a.predicted_reduction,
                            reason="signature_changed"))
                continue
            delta = float(acts[0].pre_runqlat - window_avg[node])
            total_pred = sum(a.predicted_reduction for a in acts)
            for a in acts:
                share = a.predicted_reduction / max(total_pred, 1e-9)
                a.realized_reduction = delta * share
                ratio = float(np.clip(
                    a.realized_reduction / max(a.predicted_reduction, 1e-9),
                    0.0, cfg.corr_max))
                old = self.corrections.get(a.kind, 1.0)
                self.corrections[a.kind] = float(np.clip(
                    (1.0 - cfg.corr_beta) * old + cfg.corr_beta * ratio,
                    cfg.corr_min, cfg.corr_max))
                m.inc("actions_verified")
                m.inc("predicted_reduction", a.predicted_reduction)
                m.inc("realized_reduction", a.realized_reduction)
                m.inc("calibration_abs_error",
                      abs(a.realized_reduction - a.predicted_reduction))
                if rec:
                    rec.emit(ActionVerified(
                        action=a.kind, action_id=a.action_id, node=node,
                        outcome="verified", predicted=a.predicted_reduction,
                        realized=a.realized_reduction,
                        correction=self.corrections[a.kind]))
                verified.append({
                    "node": node, "kind": a.kind,
                    "predicted": a.predicted_reduction,
                    "realized": a.realized_reduction,
                    "correction": self.corrections[a.kind],
                })
        self._to_verify = []
        self._verify_sig = {}
        return verified

    def _reconcile_slot_tenants(self, view) -> None:
        """Reset detector attribution for slots whose tenant changed.

        The detector's slot track is keyed by (node, slot), but slots are
        reused: the simulator places, migrates, and evicts into them.
        Diffing consecutive ``slot_uids`` snapshots keys the track on the
        *tenant* — a new arrival starts from a clean slate instead of
        inheriting the decayed drift score (and being blamed for) its
        predecessor's incident.  (The forecast service does its own
        tenant-keyed clearing inside ``observe``.)
        """
        if view.slot_uids is None:
            return
        uids = np.asarray(view.slot_uids)
        prev, self._slot_uids = self._slot_uids, uids
        if prev is None or prev.shape != uids.shape:
            return
        nodes, slots = np.nonzero(uids != prev)
        if nodes.size == 0:
            return
        self.detector.clear_slots(nodes, slots)

    def _forecast(self, view, window_avg):
        """Project each node's runqlat ``horizon`` windows ahead.

        Delegates to the shared ``ForecastService``: feeds it this window's
        view (idempotent if the driver already did) and converts its
        projection into the detector's forecast channel input — nodes the
        model says will get MEANINGFULLY worse get ``window_avg + delta``,
        the rest the no-forecast sentinel so their f_cusum cannot tip on a
        flat projection of an already-warm node.  Returns ``(None, None)``
        while the channel is off or not yet warmed up.
        """
        svc = self.forecast_service
        if not self.cfg.proactive or svc is None or view.online_qps is None:
            return None, None
        svc.observe(view)
        proj = svc.project(view)
        if proj is None:
            return None, None  # need two windows to know the cadence
        forecast_avg = np.where(
            proj.delta >= svc.cfg.min_predicted_drift,
            window_avg + proj.delta, -1e9)
        return forecast_avg, proj.rho

    def step(self, cluster, view=None) -> list[Action]:
        """One control iteration; returns the actions actually applied.

        ``view``: the ``ClusterView`` for the telemetry window that just
        ended — drivers that already built one (e.g. ``run_experiment``,
        which shares it with the forecast service) pass it in; standalone
        callers let the loop snapshot the cluster itself.
        """
        if (self.detector is None or self.detector.n != cluster.n
                or self._cluster_ref() is not cluster):
            self.reset()
            self.detector = StreamingDetector(cluster.n, self.cfg.detector)
            self._cluster_ref = weakref.ref(cluster)
        if view is None:
            view = cluster.view()
        slot_hists = view.slot_hists
        if slot_hists is None:
            slot_hists = np.concatenate(
                [view.online_hists, view.offline_hists], axis=1)
        # slot reuse since last step invalidates per-slot tracks: clear them
        # BEFORE this window's update so the new tenant's first histogram is
        # scored as an arrival jump, not summed into the predecessor's decay
        self._reconcile_slot_tenants(view)
        # raw last-window node average (NOT the detector's decayed estimate):
        # verification compares like with like across two adjacent windows
        window_avg = view.node_runqlat_avg()
        with self.timers.phase("verify"):
            verified = self._verify(cluster, window_avg)
        with self.timers.phase("forecast"):
            forecast_avg, forecast_rho = self._forecast(view, window_avg)
        with self.timers.phase("detect"):
            hot = self.detector.update(slot_hists, forecast_avg)
        pro = self.detector.last_proactive
        if pro is None:
            pro = np.zeros(cluster.n, bool)
        m = self.metrics
        rec = self._recorder
        step_no = int(m.inc("steps"))
        m.inc("hotspots_flagged", int(hot.sum()))
        m.inc("proactive_flagged", int(pro.sum()))
        if rec and (hot.any() or pro.any()):
            self._emit_hotspots(hot, pro)

        # flags consumed on a slower cadence than they are produced stay
        # pending for one acting interval, so interval > 1 can't lose them.
        # Flags raised while a node is in post-action cooldown DO expire:
        # that is deliberate hysteresis — the node was just mitigated, and
        # if it is still genuinely hot the drift re-accumulates (or the
        # acute p-tail path refires) once telemetry reflects the action
        for node in np.nonzero(hot)[0]:
            self._pending[int(node)] = step_no
            self._pending_pro.pop(int(node), None)  # reactive outranks
        for node in np.nonzero(pro)[0]:
            if int(node) not in self._pending:
                self._pending_pro[int(node)] = step_no
        keep = lambda d: {n: s for n, s in d.items()  # noqa: E731
                          if step_no - s < self.cfg.interval}
        self._pending = keep(self._pending)
        self._pending_pro = keep(self._pending_pro)

        # a freshly-mitigated node gets cooldown steps for its telemetry to
        # reflect the action before we pile on more mitigations (anti-thrash)
        actionable = np.zeros(cluster.n, bool)
        actionable[list(self._pending)] = True
        actionable[list(self._pending_pro)] = True
        for node, step in self._last_acted.items():
            if step_no - step < self.cfg.cooldown:
                actionable[node] = False
        proactive_mask = np.zeros(cluster.n, bool)
        proactive_mask[list(self._pending_pro)] = True
        proactive_mask &= actionable

        applied: list[Action] = []
        plan: list[Action] = []
        declined = {} if self.outcomes is not None else None
        if actionable.any() and step_no % self.cfg.interval == 0:
            recently_acted = frozenset(
                uid for uid, step in self._uid_last_acted.items()
                if step_no - step < self.cfg.uid_cooldown
            )
            with self.timers.phase("plan"):
                plan = self.policy.plan(cluster, view, actionable,
                                        exclude_uids=recently_acted,
                                        corrections=self.corrections,
                                        attribution=self.detector.attribution(),
                                        proactive=proactive_mask,
                                        forecast_pressure=forecast_rho,
                                        recorder=rec, declined=declined)
            m.inc("actions_planned", len(plan))
            for action in plan:
                if action.apply(cluster):
                    applied.append(action)
                    action.pre_runqlat = float(window_avg[action.node])
                    if action.proactive:
                        # no post-window check: the window this action
                        # mitigates is horizon steps ahead, and judging it
                        # on next window's delta would poison the per-kind
                        # corrections with structurally-absent relief
                        m.inc("proactive_applied")
                    else:
                        self._to_verify.append(action)
                    m.inc("actions_applied")
                    m.inc(f"applied_kind.{action.kind}")
                    if not action.proactive:
                        # proactive actions skip the node cooldown: they are
                        # gentle bets placed BEFORE the worst window, and if
                        # the incident still develops the reactive track
                        # must be free to respond immediately — per-pod
                        # uid_cooldown already prevents ping-pong
                        self._last_acted[action.node] = step_no
                    self._pending.pop(action.node, None)
                    self._pending_pro.pop(action.node, None)
                    uid = getattr(action, "uid", -1)
                    if uid >= 0:
                        self._uid_last_acted[uid] = step_no
                    if rec:
                        rec.emit(ActionExecuted(
                            action=action.kind, action_id=action.action_id,
                            node=action.node, uid=uid,
                            dst=getattr(action, "dst", -1),
                            proactive=action.proactive,
                            pre_runqlat=action.pre_runqlat,
                            predicted_reduction=action.predicted_reduction))
            for node in {a.node for a in applied if not a.proactive}:
                self._verify_sig[node] = self._node_signature(cluster, node)
        if self.outcomes is not None:
            self._record_outcomes(step_no, view, hot, pro, actionable, plan,
                                  applied, declined)
        if hot.any() or pro.any() or applied or verified:
            self.history.append({
                "step": step_no,
                "window": rec.window if rec else step_no - 1,
                "t": float(view.t),
                "hot_nodes": np.nonzero(hot)[0].tolist(),
                "proactive_nodes": np.nonzero(pro)[0].tolist(),
                "hot_slots": self.detector.hot_slots(),
                "applied": [a.describe() for a in applied],
                "verified": verified,
            })
        return applied

    def _record_outcomes(self, step_no: int, view, hot, pro, actionable,
                         plan: list[Action], applied: list[Action],
                         declined: dict) -> None:
        """Append this window's outcome record to ``outcomes``.

        ``hot``/``proactive`` are the detector's flags; ``flagged`` holds,
        for each flagged node, the actions planned on it (kind, cost,
        whether ``apply`` took) and, when none took, the guard that
        declined it: ``cooldown`` (acted on at step ``last_acted``, under
        ``cooldown`` steps ago), ``interval`` (not an acting step),
        ``apply_failed``, or the policy's guard (``MitigationPolicy.plan``'s
        ``declined``).  ``spent`` is the cost of everything planned.
        """
        took = {id(a) for a in applied}
        flagged = []
        for node in np.nonzero(hot | pro)[0]:
            node = int(node)
            acts = [{"kind": a.kind, "cost": float(a.cost),
                     "proactive": bool(a.proactive), "applied": id(a) in took}
                    for a in plan if a.node == node]
            entry = {"node": node,
                     "channel": "hot" if hot[node] else "proactive",
                     "actions": acts, "guard": None}
            if any(a["applied"] for a in acts):
                pass  # handled: no guard to name
            elif not actionable[node]:
                entry.update(guard="cooldown",
                             last_acted=self._last_acted[node])
            elif step_no % self.cfg.interval:
                entry["guard"] = "interval"
            elif acts:
                entry["guard"] = "apply_failed"
            else:
                entry.update(declined.get(node, {}))
            flagged.append(entry)
        self.outcomes.append({
            "step": step_no, "t": float(view.t),
            "window_ticks": view.window_ticks,
            "hot": np.nonzero(hot)[0].tolist(),
            "proactive": np.nonzero(pro)[0].tolist(),
            "spent": float(sum(a.cost for a in plan)),
            "flagged": flagged,
        })

    def _emit_hotspots(self, hot: np.ndarray, pro: np.ndarray) -> None:
        """One HotspotFlag per flagged node, from the detector diagnostics.

        ``cusum``/``f_cusum`` are the pre-consumption trip values the diag
        exposes for exactly this purpose (the live accumulators read zero
        on every flag — flagging consumes them).
        """
        rec = self._recorder
        if not rec:
            return
        diag = self.detector.last_diag
        slots = self.detector.hot_slots()
        scores = self.detector.slot_scores
        for node in np.nonzero(hot | pro)[0]:
            node = int(node)
            if pro[node]:
                channel = "forecast"
            elif diag["drift_hot"][node]:
                channel = "drift"
            else:
                channel = "acute"
            slot = slots.get(node, -1)
            rec.emit(HotspotFlag(
                node=node, channel=channel,
                avg=float(diag["avg"][node]), mu=float(diag["mu"][node]),
                p_tail=float(diag["p_tail"][node]),
                cusum=float(diag["cusum_trip"][node]),
                f_cusum=float(diag["f_cusum_trip"][node]),
                slot=slot,
                slot_score=float(scores[node, slot]) if slot >= 0 else 0.0,
            ))

    def run(self, cluster, num_ticks: int, k: int | None = None) -> ControlStats:
        """Interleave rollout and control every ~k ticks (standalone driver).

        rollout rounds tick counts up to Cluster.CHUNK multiples, so progress
        is tracked via the simulator clock, not the requested k.  A rollout
        that advances the clock by zero ticks (e.g. a cluster whose chunking
        rounds a small remainder down to nothing) would loop forever; that
        is an error, not a wait state.
        """
        k = k or cluster.CHUNK
        done = 0
        rec = self._recorder
        # the scanned single-dispatch path when the cluster provides it
        # (bit-identical to the chunk loop); plain rollout otherwise
        roll = getattr(cluster, "rollout_scan", cluster.rollout)
        while done < num_ticks:
            t0 = cluster.t
            with self.timers.phase("rollout"):
                # async dispatch: block inside the timed region so the
                # device compute is attributed to "rollout", not to
                # whichever later phase happens to synchronize first
                out = roll(min(k, num_ticks - done))
                jax.block_until_ready(out)
            progress = int(cluster.t - t0)
            if progress <= 0:
                raise RuntimeError(
                    f"cluster.rollout made no progress at t={cluster.t!r} "
                    f"({done}/{num_ticks} ticks done): refusing to spin "
                    f"forever — check num_ticks vs the cluster's chunking"
                )
            done += progress
            if rec:
                rec.begin_window(cluster.t)
            self.step(cluster)
            tw = self.timers.pop_window()
            if rec and tw:
                rec.emit(PhaseTimings(timings=tw))
        return self.stats


# ---------------------------------------------------------------------------
# Per-scheduler control profiles (closes PR 2's "mitigation hurts RR/HUP"
# grid cells).  The default guards were tuned against ICO placements, which
# concentrate headroom by design; RR spreads pods uniformly and HUP packs by
# utilization, so under those placements the same guards chase seasonal
# troughs across near-symmetric nodes — each migration stacks load on a node
# that is about to warm up, and p99 ends up WORSE than no mitigation on some
# seeds.  The conservative profile demands more evidence (higher drift
# threshold), a bigger predicted gap before moving a pod (migrate_margin),
# longer per-pod cooldowns, and a smaller per-invocation budget; under it
# mitigation is non-harmful for RR/HUP on the seeds where PR 2 regressed
# while ICO/LQP keep the aggressive defaults that won them -38% p99.
# ---------------------------------------------------------------------------


SCHEDULER_PROFILES: dict[str, ControlLoopConfig] = {
    "ICO": ControlLoopConfig(),
    # ICO-F shares ICO's placement quality (it IS ICO until the forecast
    # gate opens, and strictly more headroom-aware afterwards), so it keeps
    # the aggressive profile
    "ICO-F": ControlLoopConfig(),
    "LQP": ControlLoopConfig(),
    # Source-relief only (no migrate / scale-out): under RR's uniform spread
    # the per-node features are near-symmetric, so the RF's predicted
    # destination gaps are noise and migrations chase seasonal troughs.
    # Merely *raising* migrate_margin was not enough — replaying the PR 2
    # grid with margin 40 still left RR 87% worse than no mitigation on
    # seed 0; dropping destination actions entirely flipped both regressed
    # seeds to clear wins (149->87, 133->106).
    "RR": ControlLoopConfig(
        uid_cooldown=8,
        detector=DetectorConfig(drift_threshold=90.0),
        policy=PolicyConfig(budget=8.0, cost_weight=1.5,
                            destination_actions=False),
    ),
    # HUP packs by utilization, which correlates with (but under-predicts)
    # pressure: its placements are sometimes already good, and on those
    # seeds any extra churn is pure downside — so beyond source-only
    # actions it gets a higher evidence bar and a smaller budget
    # (88->88 tie on the good seed, 228->83 on the bad one).
    "HUP": ControlLoopConfig(
        uid_cooldown=8,
        detector=DetectorConfig(drift_threshold=120.0),
        policy=PolicyConfig(budget=6.0, cost_weight=2.0,
                            destination_actions=False),
    ),
}


def scheduler_loop_config(scheduler: str,
                          proactive: bool = False) -> ControlLoopConfig:
    """Tuned ControlLoopConfig for a scheduler (default for unknown names).

    ``proactive=True`` switches on the forecast channel on top of whatever
    profile the scheduler gets.
    """
    cfg = SCHEDULER_PROFILES.get(scheduler, ControlLoopConfig())
    if proactive:
        cfg = dataclasses.replace(cfg, proactive=True)
    return cfg
