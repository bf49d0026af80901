"""Streaming hotspot detector over per-node, per-slot runqlat telemetry.

The Data Collection Module already emits, every rollout window, one
Eq.(1)-style 200-bin runqlat histogram per (node, slot).  The detector
folds those into exponentially-decayed histograms (so quantile estimates
track the recent past, not the whole run) at two granularities:

*Node track* — the slot histograms summed per node feed a one-sided CUSUM
drift statistic on the decayed average:

    cusum_t = max(0, cusum_{t-1} + (avg_t - mu_t - slack))

where ``mu`` is a slow EWMA baseline of the node's average runqlat.  A node
is flagged as a hotspot when its CUSUM crosses the drift threshold (a
sustained upward shift) or its decayed p95 crosses an absolute ceiling (an
acute spike).  Flagging consumes the accumulated drift — on the *raw*
(pre-warmup-mask) flag, so drift accumulated across the warmup transient
cannot fire a spurious flag at exactly ``steps == warmup``.

*Slot track* — each slot keeps its own decayed histogram and a
recency-weighted drift score accumulating the positive increments of its
decayed average:

    score_t = decay * score_{t-1} + max(0, s_avg_t - s_avg_{t-1})

A pod that lands mid-incident jumps its slot's average from zero to the
hot node's level in one window, so the slot that *started* the drift (the
arriving offender) outranks long-resident slots that merely rose with it;
the decay forgets old incidents so attribution always reflects the current
one.  A hotspot flag therefore carries the (node, slot) whose runqlat
drifted (``slot_scores`` / ``hot_slots``), and the mitigation policy picks
victims from it directly instead of per-node heuristics.  Attribution is
keyed on the slot's *tenant*: the ControlLoop calls ``clear_slots`` when a
pod is placed into, migrated into, or evicted from a slot, so a reused
slot never inherits its predecessor's drift score; and below
``attribution_floor`` (an acute p-tail flag with no drift leaves every
score near zero) the detector returns no attribution at all rather than a
meaningless ``argmax`` of noise — the policy falls back to its
pressure/QPS heuristics.

*Forecast track* — ``update`` optionally takes ``forecast_avg``: the node
runqlat the seasonal QPS forecaster projects ``horizon`` windows ahead
(``repro.control.forecast``).  A second one-sided CUSUM accumulates the
*predicted* exceedance against the same observed baseline ``mu``:

    f_cusum_t = max(0, f_cusum_{t-1} + (forecast_avg_t - mu_t - slack))

and crossing ``proactive_threshold`` raises a *proactive* flag
(``last_proactive``) — the hotspot has not formed yet, but the model says
it will, so mitigation can land before the worst window instead of after
it.  Reactive flags take precedence (a node already hot is not "proactive"),
and either flag consumes both accumulators.

The whole update — decay, quantiles, baseline, both CUSUMs, slot scores,
flags — is a single jit'd call over all N nodes and S slots; there is no
per-node Python loop, so the detector scales to thousands of nodes exactly
like the scheduler hot path.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metric


@dataclasses.dataclass(frozen=True)
class DetectorConfig:
    decay: float = 0.5        # per-update decay of the accumulated histograms
    baseline_alpha: float = 0.05  # EWMA rate of the drift baseline mu
    slack: float = 8.0        # CUSUM allowance (latency units above baseline)
    drift_threshold: float = 60.0  # cumulative drift (latency units) to flag
    quantile: float = 95.0    # tracked tail quantile
    abs_threshold: float = 400.0   # acute p-quantile ceiling (latency units)
    warmup: int = 2           # updates before flags are allowed
    proactive_threshold: float = 60.0  # forecast-CUSUM level for a proactive
                                       # flag; matches drift_threshold so the
                                       # predicted incident must look as real
                                       # as an observed one
    attribution_floor: float = 5.0     # min slot score to name a culprit: an
                                       # acute flag with no drift leaves all
                                       # scores ~0 and argmax would blame
                                       # slot 0 arbitrarily


def node_track_step(hist, mu, cusum, steps, node_hists, decay, alpha, slack,
                    drift_thr, q, abs_thr, warmup):
    """Pure node-track CUSUM step: decay the histogram, move the EWMA
    baseline, accumulate drift, trip/consume the flags.

    Shared by ``_detector_update`` (the host loop's jit'd call) and the
    scanned rollout core (``repro.cluster.state.batched_rollout`` folds
    this into its window carry) — one definition, so the in-scan detector
    is the same math as the interactive one.  Returns
    (hist, avg, p_tail, mu, cusum_after_reset, cusum_trip, drift_trip,
    acute_trip, raw_hot, hot); the caller owns the ``steps`` increment.
    """
    hist = hist * decay + node_hists
    avg = metric.avg_runqlat(hist)
    p_tail = metric.percentile(hist, q)

    # first observation seeds the baseline; afterwards it moves slowly so a
    # genuine drift accumulates in the CUSUM before the baseline absorbs it
    mu = jnp.where(steps == 0, avg, (1.0 - alpha) * mu + alpha * avg)
    cusum = jnp.maximum(cusum + (avg - mu - slack), 0.0)

    drift_trip = cusum > drift_thr
    acute_trip = p_tail > abs_thr
    raw_hot = drift_trip | acute_trip
    hot = raw_hot & (steps >= warmup)

    # hysteresis: a flag consumes the accumulated drift, so a node must
    # re-accumulate before flagging again (the acute p_tail path still
    # refires).  The reset keys on the RAW flag: suppressing only the mask
    # during warmup would leave the warmup transient's drift in cusum and
    # fire a spurious flag at exactly steps == warmup.
    cusum_trip = cusum
    cusum = jnp.where(raw_hot, 0.0, cusum)
    return (hist, avg, p_tail, mu, cusum, cusum_trip, drift_trip, acute_trip,
            raw_hot, hot)


@jax.jit
def _detector_update(hist, mu, cusum, f_cusum, slot_hist, slot_prev,
                     slot_score, steps, slot_hists, forecast_avg, decay,
                     alpha, slack, drift_thr, pro_thr, q, abs_thr, warmup):
    """One detector step for all nodes and slots at once.

    hist (N, 200), mu (N,), cusum/f_cusum (N,), slot_hist (N, S, 200),
    slot_prev/slot_score (N, S), steps () int32; slot_hists (N, S, 200)
    fresh per-slot counts from the last telemetry window; forecast_avg (N,)
    projected node runqlat (a large negative sentinel when no forecast is
    available, so f_cusum stays pinned at zero).  Returns the new state
    plus the hotspot/proactive masks and a diagnostics dict.
    """
    node_hists = slot_hists.sum(1)
    (hist, avg, p_tail, mu, cusum, cusum_trip, drift_trip, acute_trip,
     raw_hot, hot) = node_track_step(hist, mu, cusum, steps, node_hists,
                                     decay, alpha, slack, drift_thr, q,
                                     abs_thr, warmup)

    # forecast channel: CUSUM of the *predicted* exceedance over the same
    # observed baseline.  A reactive flag outranks a proactive one, and
    # either consumes both accumulators (a node just flagged — for real or
    # ahead of time — must re-accumulate evidence before flagging again).
    # The flag additionally requires observed corroboration — the node's
    # decayed average already above baseline+slack — so a model-only
    # prediction on a perfectly calm node cannot trigger churn; the lead
    # over the reactive track comes from f_cusum accumulating faster than
    # cusum during the incident's leading edge, not from pure speculation.
    f_cusum = jnp.maximum(f_cusum + (forecast_avg - mu - slack), 0.0)
    raw_pro = (f_cusum > pro_thr) & (avg > mu + slack)
    proactive = raw_pro & (steps >= warmup) & ~raw_hot

    # node_track_step already consumed the drift CUSUM on the raw flag; the
    # forecast accumulator is consumed here on either flag (the ControlLoop
    # keeps un-acted flags pending across an interval skip so incidents
    # aren't lost to acting cadence)
    f_cusum_trip = f_cusum  # pre-consumption value: what the flag tripped on
    f_cusum = jnp.where(raw_hot | raw_pro, 0.0, f_cusum)

    # slot track: decayed per-slot histogram + recency-weighted positive
    # drift of its average.  A vacated slot's decayed average is invariant
    # under decay (numerator and denominator shrink together) so it stops
    # scoring; a pod landing in a slot jumps the average and scores the
    # full jump, which is exactly the arriving-offender signal we want.
    slot_hist = slot_hist * decay + slot_hists
    s_avg = metric.avg_runqlat(slot_hist)
    slot_score = decay * slot_score + jnp.maximum(s_avg - slot_prev, 0.0)
    slot_prev = s_avg

    diag = {"avg": avg, "p_tail": p_tail, "mu": mu, "cusum": cusum,
            "f_cusum": f_cusum, "slot_avg": s_avg, "slot_score": slot_score,
            # trace-facing: pre-reset trip values and per-channel masks, so
            # a HotspotFlag event can say which statistic fired and at what
            # level (the post-reset cusum above reads 0 on every flag)
            "cusum_trip": cusum_trip, "f_cusum_trip": f_cusum_trip,
            "drift_hot": drift_trip & (steps >= warmup),
            "acute_hot": acute_trip & (steps >= warmup)}
    return (hist, mu, cusum, f_cusum, slot_hist, slot_prev, slot_score,
            steps + 1, hot, proactive, diag)


def slot_mask(shape, nodes, slots) -> np.ndarray:
    """(N, S) bool mask of the (node, slot) pairs, built on the host: a
    masked update of this fixed shape compiles once, where a scatter
    compiles once per index count."""
    mask = np.zeros(shape, bool)
    mask[nodes, slots] = True
    return mask


@jax.jit
def _clear_slot_track(slot_hist, slot_prev, slot_score, mask):
    """Zero the slot track where ``mask`` is set (bitwise the scatter of
    0.0 into those slots)."""
    return (jnp.where(mask[..., None], 0.0, slot_hist),
            jnp.where(mask, 0.0, slot_prev),
            jnp.where(mask, 0.0, slot_score))


class StreamingDetector:
    """Host-side wrapper owning the detector state for one cluster."""

    def __init__(self, num_nodes: int, config: DetectorConfig | None = None):
        self.cfg = config or DetectorConfig()
        self.n = num_nodes
        self.reset()

    def reset(self) -> None:
        self.hist = jnp.zeros((self.n, metric.NUM_BINS), jnp.float32)
        self.mu = jnp.zeros((self.n,), jnp.float32)
        self.cusum = jnp.zeros((self.n,), jnp.float32)
        self.f_cusum = jnp.zeros((self.n,), jnp.float32)
        self.steps = jnp.int32(0)
        # slot-track state is shaped by the first update (S is a property
        # of the telemetry, not of the cluster size)
        self.num_slots: int | None = None
        self.slot_hist = None
        self.slot_prev = None
        self.slot_score = None
        self.slot_scores: np.ndarray | None = None  # (N, S) after update()
        self.last_hot: np.ndarray | None = None
        self.last_proactive: np.ndarray | None = None
        self.last_diag: dict | None = None

    def _ensure_slots(self, num_slots: int) -> None:
        if self.num_slots == num_slots:
            return
        self.num_slots = num_slots
        self.slot_hist = jnp.zeros((self.n, num_slots, metric.NUM_BINS),
                                   jnp.float32)
        self.slot_prev = jnp.zeros((self.n, num_slots), jnp.float32)
        self.slot_score = jnp.zeros((self.n, num_slots), jnp.float32)

    def clear_slots(self, nodes, slots) -> None:
        """Forget the attribution track of (node, slot) pairs.

        Called by the ControlLoop whenever a slot's tenant changes (place /
        migrate / evict): the decayed histogram and drift score belong to
        the departed pod, and without the clear a reused slot inherits its
        predecessor's score via decay only — the new tenant can be blamed
        for an incident it never caused and evicted wrongly.
        """
        if self.slot_hist is None:
            return
        nodes = np.asarray(nodes, np.int64).ravel()
        slots = np.asarray(slots, np.int64).ravel()
        if nodes.size == 0:
            return
        mask = slot_mask(self.slot_prev.shape, nodes, slots)
        self.slot_hist, self.slot_prev, self.slot_score = _clear_slot_track(
            self.slot_hist, self.slot_prev, self.slot_score, mask)
        if self.slot_scores is not None:
            scores = np.array(self.slot_scores)  # may be a read-only view
            scores[nodes, slots] = 0.0
            self.slot_scores = scores

    def update(self, hists, forecast_avg=None) -> np.ndarray:
        """Feed one window of runqlat histograms; returns hotspot mask (N,).

        hists: (N, S, 200) per-slot counts (full attribution) or (N, 200)
        node-level counts (treated as a single slot; node behaviour is
        identical either way because the node track sums over slots).
        forecast_avg: optional (N,) projected node runqlat ``horizon``
        windows ahead; drives the proactive channel (``last_proactive``).
        Without it the forecast CUSUM stays pinned at zero.
        """
        c = self.cfg
        hists = jnp.asarray(hists, jnp.float32)
        if hists.ndim == 2:
            hists = hists[:, None, :]
        self._ensure_slots(hists.shape[1])
        if forecast_avg is None:
            # large negative sentinel: the increment is always < 0, so the
            # forecast CUSUM clamps to zero and no proactive flag can fire
            forecast_avg = jnp.full((self.n,), -1e9, jnp.float32)
        else:
            forecast_avg = jnp.asarray(forecast_avg, jnp.float32)
        (self.hist, self.mu, self.cusum, self.f_cusum, self.slot_hist,
         self.slot_prev, self.slot_score, self.steps, hot, proactive,
         diag) = _detector_update(
            self.hist, self.mu, self.cusum, self.f_cusum, self.slot_hist,
            self.slot_prev, self.slot_score, self.steps, hists, forecast_avg,
            c.decay, c.baseline_alpha, c.slack, c.drift_threshold,
            c.proactive_threshold, c.quantile, c.abs_threshold, c.warmup,
        )
        self.last_diag = {k: np.asarray(v) for k, v in diag.items()}
        self.slot_scores = self.last_diag["slot_score"]
        self.last_hot = np.asarray(hot)
        self.last_proactive = np.asarray(proactive)
        return self.last_hot

    def hot_slots(self) -> dict[int, int]:
        """Attribution of the last update: flagged node -> drifted slot.

        Nodes whose best slot score sits under ``attribution_floor`` are
        omitted: an acute p-tail flag with no drift leaves every score near
        zero, and argmax over noise would silently blame slot 0.
        """
        if self.last_hot is None or self.slot_scores is None:
            return {}
        floor = self.cfg.attribution_floor
        out: dict[int, int] = {}
        for n in np.nonzero(self.last_hot)[0]:
            s = int(np.argmax(self.slot_scores[n]))
            if self.slot_scores[n, s] >= floor:
                out[int(n)] = s
        return out

    def attribution(self) -> np.ndarray | None:
        """Slot scores with sub-floor entries zeroed, for the policy.

        A zero score means "no attribution": the policy's drift ranking
        degrades to its pressure/QPS heuristics instead of keying victim
        selection on meaningless noise.
        """
        if self.slot_scores is None:
            return None
        floor = self.cfg.attribution_floor
        return np.where(self.slot_scores >= floor, self.slot_scores, 0.0)
