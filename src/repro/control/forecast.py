"""Online seasonal QPS forecaster driving proactive mitigation.

The reactive control loop only acts after a node's runqlat has already
drifted, so online pods eat the full latency of every incident's leading
edge.  The QPS traces the simulator replays carry a dominant diurnal
component plus a half-day harmonic (``repro.cluster.trace``), which makes
the near future of each pod's load trivially forecastable — and the
delay-curve model already maps load to runqlat.  This module closes that
gap:

*Forecaster* — every pod keeps a decayed least-squares regression of its
observed window-mean QPS onto diurnal harmonic features

    x(t) = [1, sin wt, cos wt, sin 2wt, cos 2wt],   w = 2*pi / TICKS_PER_DAY

with moments A = sum decay^k x x^T and b = sum decay^k x y, so the fit
tracks the recent trace rather than the whole run.  The update — one-step
error scoring of the previous fit, then the moment update — runs for all
(node, slot) pods in a single jit'd call, mirroring the detector's
no-Python-loop style; ``forecast(t')`` solves the (ridge-regularized)
normal equations batched and evaluates the harmonics at the future time.

*Confidence gate* — a forecast is only trusted after ``min_windows``
observations AND while the EWMA of the one-step relative prediction error
stays under ``max_rel_err``.  Pods failing the gate contribute their
*current* QPS to any projection, i.e. they predict "no change" rather than
noise; this is what keeps a noisy or newly-landed pod from churning the
proactive channel.

*Projection* — ``project_node_pressure`` pushes per-slot QPS (observed or
forecast) through the same linear resource model and M/G/1-PS delay curve
the simulator and the mitigation policy use, giving the node runqlat the
model expects at that load.  The ControlLoop feeds the detector the
*difference* between the projections at forecast and current QPS, added to
the observed window average — a bias-free drift estimate (any systematic
model/observation offset cancels) on which the detector's forecast-CUSUM
channel raises ``proactive`` flags before the hotspot materializes.

*Service* — ``ForecastService`` packages the forecaster, the telemetry
cadence tracking, the tenant-keyed fit invalidation, and the projection
into one shared object over ``repro.cluster.ClusterView`` snapshots.  The
mitigation loop and the ICO-F admission path consume the same instance, so
runtime correction and placement price contention with a single model and
a single trust gate — and ``state_dict``/``load_state_dict`` warm-start a
later run from a prior run's fit instead of re-earning the leverage gate
over a fresh diurnal period.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster import simulator as sim
from repro.cluster.workloads import online_arrays
from repro.control.detector import slot_mask
from repro.control.policy import node_delay_curve, view_delay_params

NUM_FEATURES = 5  # [1, sin wt, cos wt, sin 2wt, cos 2wt]
_OMEGA = 2.0 * np.pi / sim.TICKS_PER_DAY


@dataclasses.dataclass(frozen=True)
class ForecastConfig:
    decay: float = 0.995      # per-window decay of the regression moments;
                              # the memory (~1/(1-decay) windows) must span
                              # at least one diurnal period or the fit only
                              # ever sees a short arc and extrapolates wildly
    ridge: float = 1.0        # Tikhonov term on the normal-equation solve
    err_alpha: float = 0.3    # EWMA rate of the one-step relative error
    min_windows: int = 6      # observations before a pod's fit is trusted
    max_rel_err: float = 0.25 # confidence gate on the one-step rel. error
    qps_floor: float = 25.0   # rel-error denominator floor (QPS units)
    max_leverage: float = 0.1 # extrapolation guard: leverage of the forecast
                              # time, x' (A + ridge*I)^-1 x.  Until the data
                              # covers enough of the period, the harmonic
                              # basis is under-determined in the forecast
                              # direction and the fit extrapolates steeply
                              # where the truth is flat — the one-step error
                              # (interpolation) cannot see this, leverage can
    rho_cap: float = 0.85     # ceiling on the *forecast* pressure: past it
                              # the delay curve is near its asymptote and a
                              # few percent of QPS forecast error explodes
                              # into hundreds of latency-units of phantom
                              # drift/relief, buying migrations reality
                              # never justifies
    min_predicted_drift: float = 3.0   # projected runqlat increase (latency
                                       # units) under which a node's forecast
                                       # is withheld from the proactive
                                       # channel: without this gate every
                                       # node near the reactive threshold
                                       # tips "proactive" on a flat forecast,
                                       # and the channel degenerates into a
                                       # lower-bar reactive detector


def _features(t):
    wt = _OMEGA * t
    return jnp.stack([jnp.ones_like(wt), jnp.sin(wt), jnp.cos(wt),
                      jnp.sin(2.0 * wt), jnp.cos(2.0 * wt)], axis=-1)


def _solve(A, b, ridge):
    eye = jnp.eye(NUM_FEATURES, dtype=A.dtype)
    return jnp.linalg.solve(A + ridge * eye, b[..., None])[..., 0]


@jax.jit
def _forecast_update(A, b, err, count, t, y, active, decay, ridge, alpha,
                     qps_floor):
    """Score the previous fit at time t, then fold in the new observation.

    A (N, S, F, F), b (N, S, F), err/count (N, S); y (N, S) window-mean QPS,
    active (N, S) bool.  Returns the new state plus the one-step prediction
    the *old* fit made for this window (the calibration signal).

    Also reused verbatim inside the scanned rollout core
    (``repro.cluster.state.batched_rollout`` folds it into the window carry),
    so the in-scan forecaster moments are the same math as this host loop's.
    """
    x = _features(t)                                   # (F,)
    pred = jnp.maximum((_solve(A, b, ridge) * x).sum(-1), 0.0)
    rel = jnp.abs(pred - y) / jnp.maximum(y, qps_floor)
    scored = active & (count > 0)
    err = jnp.where(scored, (1.0 - alpha) * err + alpha * rel, err)
    xx = x[:, None] * x[None, :]
    A = jnp.where(active[..., None, None], decay * A + xx, A)
    b = jnp.where(active[..., None], decay * b + x * y[..., None], b)
    count = jnp.where(active, count + 1, count)
    return A, b, err, count, pred


@jax.jit
def _forecast_eval(A, b, t_future, ridge):
    x = _features(t_future)
    return jnp.maximum((_solve(A, b, ridge) * x).sum(-1), 0.0)


@jax.jit
def _leverage(A, t_future, ridge):
    """x' (A + ridge*I)^-1 x at the forecast time, batched over (N, S)."""
    xb = jnp.broadcast_to(_features(t_future),
                          A.shape[:-2] + (NUM_FEATURES,))
    return (xb * _solve(A, xb, ridge)).sum(-1)


@jax.jit
def _clear_fits(A, b, err, count, mask):
    """Reset the fits where the (N, S) ``mask`` is set: one program for
    every number of cleared slots, bitwise the scatter it replaces."""
    return (jnp.where(mask[..., None, None], 0.0, A),
            jnp.where(mask[..., None], 0.0, b),
            jnp.where(mask, 1.0, err),
            jnp.where(mask, 0, count))


class QPSForecaster:
    """Host-side wrapper owning per-(node, slot) forecast state."""

    def __init__(self, num_nodes: int, num_slots: int,
                 config: ForecastConfig | None = None):
        self.cfg = config or ForecastConfig()
        self.n = num_nodes
        self.s = num_slots
        self.reset()

    def reset(self) -> None:
        F = NUM_FEATURES
        self.A = jnp.zeros((self.n, self.s, F, F), jnp.float32)
        self.b = jnp.zeros((self.n, self.s, F), jnp.float32)
        # err starts at 1.0 (fully untrusted) and must be *earned* down
        # through min_windows good one-step predictions
        self.err = jnp.ones((self.n, self.s), jnp.float32)
        self.count = jnp.zeros((self.n, self.s), jnp.int32)
        self.last_pred: np.ndarray | None = None

    def clear_slots(self, nodes, slots) -> None:
        """Forget a slot's fit — its tenant changed; the history is not his."""
        nodes = np.asarray(nodes, np.int64).ravel()
        slots = np.asarray(slots, np.int64).ravel()
        if nodes.size == 0:
            return
        mask = slot_mask((self.n, self.s), nodes, slots)
        self.A, self.b, self.err, self.count = _clear_fits(
            self.A, self.b, self.err, self.count, mask)

    def update(self, t: float, qps, active) -> np.ndarray:
        """Feed one window's mean QPS; returns the one-step EWMA errors."""
        c = self.cfg
        qps = jnp.asarray(qps, jnp.float32)
        active = jnp.asarray(active, bool)
        self.A, self.b, self.err, self.count, pred = _forecast_update(
            self.A, self.b, self.err, self.count, jnp.float32(t), qps, active,
            c.decay, c.ridge, c.err_alpha, c.qps_floor,
        )
        self.last_pred = np.asarray(pred)
        return np.asarray(self.err)

    def forecast(self, t_future: float) -> np.ndarray:
        """Per-pod QPS the harmonic fits project at a future tick time."""
        return np.asarray(_forecast_eval(
            self.A, self.b, jnp.float32(t_future), self.cfg.ridge))

    def confidence(self, t_future: float | None = None) -> np.ndarray:
        """(N, S) bool: pods whose forecast passes the confidence gate.

        With ``t_future`` the gate also requires low *leverage* at the
        forecast time — rejecting extrapolations into a direction of the
        harmonic basis the observed arc has not yet pinned down, which the
        one-step (interpolation) error is structurally blind to.
        """
        c = self.cfg
        ok = ((np.asarray(self.count) >= c.min_windows)
              & (np.asarray(self.err) <= c.max_rel_err))
        if t_future is not None:
            lev = np.asarray(_leverage(self.A, jnp.float32(t_future), c.ridge))
            ok &= lev <= c.max_leverage
        return ok

    def calibration_error(self) -> float:
        """Mean one-step relative error over pods with enough history."""
        mature = np.asarray(self.count) >= self.cfg.min_windows
        if not mature.any():
            return float("nan")
        return float(np.asarray(self.err)[mature].mean())


def project_node_pressure(view, qps) -> np.ndarray:
    """Burst-weighted run-queue pressure each node would carry at the given
    per-slot online QPS (offline pressure taken from the current window).

    ``view`` is a ``repro.cluster.ClusterView`` (or anything exposing its
    ``on_type`` / ``on_active`` / ``off_pressure`` / ``cpu_sum`` fields).
    Evaluating this at observed vs forecast QPS and differencing the delay
    curve gives the predicted runqlat drift, free of model bias.
    """
    arrs = online_arrays()
    on_type = np.asarray(view.on_type)
    active = np.asarray(view.on_active, bool)
    qps = np.asarray(qps, np.float64)
    cpu_on = np.where(
        active,
        arrs["cpu_per_qps"][on_type] * qps + arrs["cpu_base"][on_type],
        0.0,
    )
    pressure = cpu_on.sum(-1) + np.asarray(view.off_pressure) + sim.OS_BASE_CORES
    return pressure / np.asarray(view.cpu_sum, np.float64)


@dataclasses.dataclass
class NodeProjection:
    """Per-node runqlat projection at the service horizon."""

    runqlat: np.ndarray   # (N,) projected node avg runqlat: observed + delta
    rho: np.ndarray       # (N,) forecast pressure, clamped at rho_cap
    delta: np.ndarray     # (N,) model delta: delay(rho_fut) - delay(rho_now)
    trusted: np.ndarray   # (N,) bool: >= 1 pod on the node passed the gate


class ForecastService:
    """Shared seasonal-projection service for mitigation AND admission.

    One ``QPSForecaster`` plus everything around it that used to live
    inside ``ControlLoop``: telemetry-cadence tracking (EWMA of ticks per
    window, needed to convert the ``horizon`` from windows to ticks),
    tenant-keyed fit invalidation (diffing consecutive ``slot_uids``
    snapshots so a reused slot never inherits its predecessor's fit), and
    the bias-cancelling projection ``y(t) + fit(t+h) - fit(t)`` pushed
    through the delay-curve model.

    The service is deliberately *shared*: the mitigation loop feeds its
    projection to the detector's forecast-CUSUM channel, and the admission
    path (``ICOFScheduler``) reads the same projection off the view via
    ``annotate`` — so placement and runtime correction price contention
    with one model, one trust gate, and one ``rho_cap`` clamp, and cannot
    fight each other over where load is heading.

    ``observe`` is idempotent per ``view.t`` (the experiment driver and the
    control loop may both observe the same window) and resets itself when
    the telemetry shape changes or the cluster clock jumps backwards (a
    different cluster, possibly of the same size).  ``state_dict`` /
    ``load_state_dict`` warm-start a later run from a prior run's fit —
    useful when replaying the same workload layout, where a cold forecaster
    would otherwise spend ~a diurnal period re-earning its leverage gate.
    """

    def __init__(self, config: ForecastConfig | None = None,
                 horizon: float = 6.0):
        self.cfg = config or ForecastConfig()
        self.horizon = float(horizon)
        self.recorder = None  # optional repro.obs.TraceRecorder; survives
                              # reset() — the trace outlives a cluster swap
        self.reset()

    def reset(self) -> None:
        self.forecaster: QPSForecaster | None = None
        self._slot_uids: np.ndarray | None = None  # last online-slot tenants
        self._last_t: float | None = None          # clock at last observe
        self._dt: float | None = None              # EWMA ticks per window
        self._trust_prev: np.ndarray | None = None  # node gate state at the
        self._trust_emit_t: float | None = None     # last traced projection

    def clear_slots(self, nodes, slots) -> None:
        """Forget fits for (node, online-slot) pairs whose tenant changed."""
        if self.forecaster is not None:
            self.forecaster.clear_slots(nodes, slots)

    def observe(self, view) -> None:
        """Fold one telemetry window's per-pod QPS into the fits.

        Idempotent per ``view.t``; diffs the view's ``slot_uids`` against
        the previous window so fits are keyed on the *tenant* (a pod
        placed, migrated, or evicted into a slot starts from scratch).

        A different cluster resets the service: a shape change is obvious,
        and a *same-shape* swap shows up as the cluster clock jumping
        backwards (each run restarts near zero) — without the reset a
        shared service would keep another cluster's fits trusted, since
        fresh uid counters also restart at 0 and defeat the tenant diff.
        Carrying fits into a new run is therefore always explicit:
        ``load_state_dict`` (warm start), never silent reuse.
        """
        qps = np.asarray(view.online_qps)
        active = np.asarray(view.on_active, bool)
        t = float(view.t)
        if (self.forecaster is not None
                and ((self.forecaster.n, self.forecaster.s) != qps.shape
                     or (self._last_t is not None and t < self._last_t))):
            self.reset()
        if self.forecaster is None:
            self.forecaster = QPSForecaster(qps.shape[0], qps.shape[1],
                                            self.cfg)
        if self._last_t is not None and t == self._last_t:
            return
        if view.slot_uids is not None:
            uids = np.asarray(view.slot_uids)[:, : qps.shape[1]]
            prev, self._slot_uids = self._slot_uids, uids
            if prev is not None and prev.shape == uids.shape:
                nodes, slots = np.nonzero(uids != prev)
                if nodes.size:
                    self.forecaster.clear_slots(nodes, slots)
        self.forecaster.update(t, qps, active)
        if self._last_t is not None and t > self._last_t:
            dt = t - self._last_t
            self._dt = dt if self._dt is None else 0.5 * self._dt + 0.5 * dt
        self._last_t = t

    def project(self, view) -> NodeProjection | None:
        """Project node runqlat ``horizon`` windows ahead of ``view.t``.

        Differencing the fit against itself at t vs t+h and applying the
        move to the *observed* QPS cancels the ridge/decay shrinkage bias;
        pods failing the confidence/leverage gate contribute their current
        QPS (they predict "no change", not noise).  Returns ``None`` while
        the channel is closed (no fits, or cadence not yet known).
        """
        if self.forecaster is None or self._dt is None:
            return None
        cfg = self.cfg
        qps_now = np.asarray(view.online_qps)
        active = np.asarray(view.on_active, bool)
        t = float(view.t)
        t_fut = t + self.horizon * self._dt
        fit_now = self.forecaster.forecast(t)
        fit_fut = self.forecaster.forecast(t_fut)
        trusted = self.forecaster.confidence(t_fut) & active
        qps_fut = np.where(trusted,
                           np.maximum(qps_now + fit_fut - fit_now, 0.0),
                           qps_now)
        rho_fut = np.minimum(project_node_pressure(view, qps_fut),
                             cfg.rho_cap)
        # per-node machine-class curve: projected relief on a big node and
        # a small node differ even at equal rho
        d_base, d_scale, d_knee = view_delay_params(view)
        delta = (node_delay_curve(rho_fut, d_base, d_scale, d_knee)
                 - node_delay_curve(project_node_pressure(view, qps_now),
                                    d_base, d_scale, d_knee))
        node_trusted = trusted.any(axis=-1)
        if self.recorder and (self._trust_emit_t is None
                              or t != self._trust_emit_t):
            # at most one transition scan per cluster time: project() may be
            # called several times for the same window (mitigation loop +
            # ICO-F annotate), and re-diffing would emit nothing new anyway
            self._emit_trust_transitions(node_trusted, trusted, t_fut)
            self._trust_emit_t = t
        return NodeProjection(
            runqlat=view.node_runqlat_avg() + delta,
            rho=rho_fut,
            delta=delta,
            trusted=node_trusted,
        )

    def _emit_trust_transitions(self, node_trusted: np.ndarray,
                                trusted: np.ndarray, t_fut: float) -> None:
        """Emit a TrustGateTransition per node whose gate just flipped."""
        if not self.recorder:
            return
        prev, self._trust_prev = self._trust_prev, node_trusted.copy()
        if prev is None or prev.shape != node_trusted.shape:
            return  # first projection (or post-reset): baseline, no events
        changed = np.nonzero(node_trusted != prev)[0]
        if changed.size == 0:
            return
        from repro.obs import TrustGateTransition
        f = self.forecaster
        lev = np.asarray(_leverage(f.A, jnp.float32(t_fut), self.cfg.ridge))
        err = np.asarray(f.err)
        count = np.asarray(f.count)
        for n in changed:
            n = int(n)
            seen = count[n] > 0  # slots with any fit history
            self.recorder.emit(TrustGateTransition(
                node=n, opened=bool(node_trusted[n]),
                leverage=float(lev[n][seen].min()) if seen.any() else np.nan,
                rel_err=float(err[n][seen].min()) if seen.any() else np.nan,
                trusted_slots=int(trusted[n].sum()),
            ))

    def annotate(self, view):
        """Fill the view's forecast fields in place (no-op while closed)."""
        proj = self.project(view)
        if proj is not None:
            view.forecast_runqlat = proj.runqlat
            view.forecast_rho = proj.rho
            view.forecast_trusted = proj.trusted
        return view

    # -------- warm start --------

    def state_dict(self) -> dict:
        """Portable snapshot of the fits for warm-starting a later run."""
        if self.forecaster is None:
            raise RuntimeError(
                "no fits to save: observe() at least one window first")
        f = self.forecaster
        return {
            "A": np.asarray(f.A), "b": np.asarray(f.b),
            "err": np.asarray(f.err), "count": np.asarray(f.count),
            "last_t": self._last_t, "dt": self._dt,
        }

    def load_state_dict(self, state: dict) -> None:
        """Adopt a prior run's fits (same workload layout assumed).

        The warm-started forecaster passes its confidence/leverage gates
        immediately instead of re-earning them over ~a diurnal period;
        ``observe`` keeps folding the new run's windows into the fit.  A
        later ``observe`` with a different telemetry shape still resets.
        ``_last_t`` is deliberately NOT restored: the new run's clock
        starts near zero, and a remembered timestamp would read as the
        clock regression ``observe`` treats as a cluster swap — loading
        state IS the explicit consent to project across runs.
        """
        A = np.asarray(state["A"])
        f = QPSForecaster(A.shape[0], A.shape[1], self.cfg)
        f.A = jnp.asarray(A, jnp.float32)
        f.b = jnp.asarray(state["b"], jnp.float32)
        f.err = jnp.asarray(state["err"], jnp.float32)
        f.count = jnp.asarray(state["count"], jnp.int32)
        self.forecaster = f
        self._slot_uids = None
        self._last_t = None
        self._dt = None if state.get("dt") is None else float(state["dt"])
