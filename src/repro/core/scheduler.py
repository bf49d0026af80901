"""ICO scheduler — paper Algorithm 1 with scoring Eqs. (4)-(6).

    score_h = (1 - utiliz_cpu_h) * (1 - utiliz_mem_h) - intf_h - intf_p      (4)
    utiliz_cpu_h = (cpu_cur_h + w_d * cpu_pod) / cpu_sum_h                    (5)
    utiliz_mem_h = (mem_cur_h + w_e * mem_pod) / mem_sum_h                    (6)

Nodes whose projected utilization exceeds the thresholds (CPU > 0.70 or
MEM > 0.80) are excluded.  The node with the highest score wins; -1 means
no feasible node (caller queues the pod).

The hot path (scoring all nodes for one pod) is a single jit'd call so the
scheduler scales to thousands of nodes; Algorithm 1's loop becomes a masked
argmax.  Eqs. (5)-(6) divide by each node's *own* capacity arrays, so a
heterogeneous fleet (``repro.cluster.fleet``) is scored per-class with no
global constants.  Past ``SchedulerConfig.candidate_k`` nodes, admission
goes sub-linear: a jit'd top-k normalized-utilization prefilter
(``repro.cluster.fleet.topk_candidates``) picks the candidate set and the
expensive interference terms run on only those k nodes.

``ICOFScheduler`` ("ICO-F") extends Eq. (4) with *projected* contention:
when the ``ClusterView`` it scores carries a forecast annotation (from
``repro.control.forecast.ForecastService``), ``intf_h`` is augmented with
the delay-curve-projected node runqlat drift at horizon — the same
projection, trust gate, and ``rho_cap`` clamp the mitigation loop prices
relief with, so admission and runtime correction can never disagree about
where contention is heading.  With the trust gate closed (no service, cold
forecaster, or no trusted pod on a node) the drift term is absent/zero and
ICO-F scores exactly like ICO.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.interference import INTF_NORM
from repro.obs import PhaseTimers


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    cpu_threshold: float = 0.70
    mem_threshold: float = 0.80
    w_d: float = 1.2  # > 1 per paper (headroom on predicted pod CPU)
    w_e: float = 1.2  # > 1 per paper (headroom on predicted pod MEM)
    # fleets larger than this go through the jit'd top-k prefilter
    # (``repro.cluster.fleet.topk_candidates``) and the expensive
    # interference terms run on only candidate_k nodes; at or below it the
    # exact all-nodes path runs, so paper-scale clusters are untouched
    candidate_k: int = 64

    def __post_init__(self):
        if not (self.w_d > 1.0 and self.w_e > 1.0):
            raise ValueError("paper requires w_d, w_e > 1.0")
        if self.candidate_k < 1:
            raise ValueError("candidate_k must be >= 1")


@partial(jax.jit, static_argnames=())
def _score_nodes(
    cpu_cur, cpu_sum, mem_cur, mem_sum, intf_h, intf_p,
    cpu_pod, mem_pod, w_d, w_e, cpu_thr, mem_thr,
):
    utiliz_cpu = (cpu_cur + w_d * cpu_pod) / cpu_sum      # Eq. (5)
    utiliz_mem = (mem_cur + w_e * mem_pod) / mem_sum      # Eq. (6)
    feasible = (utiliz_cpu <= cpu_thr) & (utiliz_mem <= mem_thr)
    score = (1.0 - utiliz_cpu) * (1.0 - utiliz_mem) - intf_h - intf_p  # Eq. (4)
    score = jnp.where(feasible, score, -jnp.inf)
    best = jnp.argmax(score)
    ok = jnp.isfinite(score[best])
    return jnp.where(ok, best, -1), score


class ICOScheduler:
    """Interference-aware Container Orchestration scheduler (Algorithm 1).

    Each offer runs in three phases, timed on ``timers`` and opened as
    ``repro.admit.<phase>`` spans once per offer: ``topk`` (the candidate
    prefilter, past ``candidate_k`` nodes only), ``quantify`` (Eq. 1's
    ``intf_nodes`` and the Eq. 3 predictor behind ``intf_pod``, plus
    ICO-F's forecast term) and ``score`` (the jit'd Eqs. 4-6 argmax).

    ``decisions`` is an opt-in decision log: set it to a list and every
    offer appends one entry holding what its choice used (see
    ``_decision``); ``None`` (the default) keeps nothing.  A
    ``recorder``'s ``AdmissionDecision`` is built from the same entry.
    """

    name = "ICO"

    def __init__(self, quantifier, config: SchedulerConfig | None = None):
        self.q = quantifier
        self.cfg = config or SchedulerConfig()
        self.recorder = None  # optional repro.obs.TraceRecorder: when set,
                              # select_node emits an AdmissionDecision with
                              # the per-node Eq. (4)-(6) breakdown
        self.decisions: list[dict] | None = None
        self.timers = PhaseTimers("repro.admit", jax.profiler.TraceAnnotation)

    def _forecast_term(self, view):
        """Per-node forecast addend to ``intf_h`` (None for plain ICO) —
        the hook ICO-F overrides."""
        return None

    def _score(self, pod, view):
        """(best global node or -1, full-length float32 scores, terms):
        ``terms`` holds the candidate indices (None when every node is
        scored) and the quantifier outputs the score used."""
        cfg = self.cfg
        idx = None
        num_nodes = view.num_nodes
        if num_nodes > cfg.candidate_k:
            # sub-linear admission: one jit'd utilization prefilter over
            # all N nodes picks candidate_k candidates, and the expensive
            # Eq. (4) interference terms run on only those.  Always a
            # fixed-size candidate set (infeasible candidates are re-masked
            # to -inf by ``_score_nodes``), so XLA compiles one (k,)-shaped
            # scorer regardless of fleet size
            from repro.cluster.fleet import topk_candidates
            with self.timers.phase("topk"):
                idx, _pre = topk_candidates(
                    jnp.asarray(view.cpu_cur, jnp.float32),
                    jnp.asarray(view.cpu_sum, jnp.float32),
                    jnp.asarray(view.mem_cur, jnp.float32),
                    jnp.asarray(view.mem_sum, jnp.float32),
                    jnp.float32(cfg.w_d * pod.cpu_demand),
                    jnp.float32(cfg.w_e * pod.mem_demand),
                    cfg.cpu_threshold, cfg.mem_threshold, cfg.candidate_k,
                )
                idx = np.asarray(idx)
            view = view.take(idx)
        with self.timers.phase("quantify"):
            intf_nodes = self.q.intf_nodes(view.online_hists,
                                           view.offline_hists)
            intf_p = self.q.intf_pod(pod.qps, view.features)
            fterm = self._forecast_term(view)
        intf_h = intf_nodes if fterm is None else np.asarray(intf_nodes) + fterm
        with self.timers.phase("score"):
            best, score = _score_nodes(
                jnp.asarray(view.cpu_cur, jnp.float32),
                jnp.asarray(view.cpu_sum, jnp.float32),
                jnp.asarray(view.mem_cur, jnp.float32),
                jnp.asarray(view.mem_sum, jnp.float32),
                jnp.asarray(intf_h, jnp.float32),
                jnp.asarray(intf_p, jnp.float32),
                jnp.float32(pod.cpu_demand),
                jnp.float32(pod.mem_demand),
                cfg.w_d, cfg.w_e, cfg.cpu_threshold, cfg.mem_threshold,
            )
            best, score = int(best), np.asarray(score)
        terms = {"nodes": idx, "intf_nodes": np.asarray(intf_nodes),
                 "intf_pod": np.asarray(intf_p),
                 "forecast_term": (None if fterm is None
                                   else np.asarray(fterm))}
        if idx is None:
            return best, score, terms
        # the best *global* node, and a full-length score array with -inf
        # outside the candidate set
        full = np.full(num_nodes, -np.inf, np.float32)
        full[idx] = score
        return (-1 if best < 0 else int(idx[best])), full, terms

    def select_node(self, pod, view) -> int:
        """Algorithm 1.

        pod: object with .qps, .cpu_demand, .mem_demand (from the Resource
             Prediction Module).
        view: ``repro.cluster.ClusterView`` — the Data Collection Module
             snapshot (cpu/mem occupancy and capacity, per-slot runqlat
             histograms, Table-III node features).
        Returns the selected node index or -1.
        """
        best, score, terms = self._score(pod, view)
        if self.decisions is not None or self.recorder:
            entry = self._decision(pod, view, score, best, terms)
            if self.decisions is not None:
                self.decisions.append(entry)
            if self.recorder:
                self.recorder.emit(self._admission_event(entry))
        return best

    def scores(self, pod, view) -> np.ndarray:
        return self._score(pod, view)[1]

    def _decision(self, pod, view, score, best: int, terms) -> dict:
        """One decision-log entry: what this offer's choice used.

        The pod's demand, the telemetry window the view covers
        (``t``, ``window_ticks``), the Eqs. (5)-(6) utilization terms and
        the feasible set (recomputed in float64 numpy from the view the
        jit'd scorer consumed), the quantifier outputs (Eq. 1's
        ``intf_nodes``, Eq. 3's ``intf_pod``) and ICO-F's ``forecast_term``
        (None for ICO or a closed gate), the scores and the chosen node.
        Every per-node array is full length; with the top-k prefilter the
        quantifier outputs are NaN and the score -inf off the candidate
        set ``nodes``.
        """
        cfg = self.cfg
        cpu_sum = np.asarray(view.cpu_sum, np.float64)
        mem_sum = np.asarray(view.mem_sum, np.float64)
        utiliz_cpu = (np.asarray(view.cpu_cur) + cfg.w_d * pod.cpu_demand) / cpu_sum
        utiliz_mem = (np.asarray(view.mem_cur) + cfg.w_e * pod.mem_demand) / mem_sum
        idx = terms["nodes"]

        def full(a, fill):
            if a is None or idx is None:
                return a
            out = np.full(view.num_nodes, fill, np.asarray(a).dtype)
            out[idx] = a
            return out

        return {
            "scheduler": self.name, "t": float(view.t),
            "window_ticks": view.window_ticks,
            "workload": pod.workload, "qps": float(pod.qps),
            "online": bool(pod.is_online),
            "cpu_demand": float(pod.cpu_demand),
            "mem_demand": float(pod.mem_demand),
            "nodes": None if idx is None else np.asarray(idx),
            "utiliz_cpu": utiliz_cpu, "utiliz_mem": utiliz_mem,
            "feasible": ((utiliz_cpu <= cfg.cpu_threshold)
                         & (utiliz_mem <= cfg.mem_threshold)),
            "intf_nodes": full(terms["intf_nodes"], np.nan),
            "intf_pod": full(terms["intf_pod"], np.nan),
            "forecast_term": full(terms["forecast_term"], np.nan),
            "score": full(score, -np.inf),
            "chosen": int(best),
        }

    def _admission_event(self, entry: dict):
        """The AdmissionDecision of one decision-log entry, with the
        Eq. (4)-(6) term breakdown: ``repro.obs.explain`` (and the
        round-trip test) reproduce the recorded ``score`` from the stored
        terms alone, without a cluster or a predictor in hand."""
        from repro.obs import AdmissionDecision
        breakdown = {
            "utiliz_cpu": entry["utiliz_cpu"],
            "utiliz_mem": entry["utiliz_mem"],
            "intf_h": entry["intf_nodes"],
            "intf_p": entry["intf_pod"],
            "feasible": entry["feasible"],
            "score": entry["score"],
        }
        if entry["forecast_term"] is not None:
            # stored apart from intf_h, so the terms decompose the score
            # without double-counting
            breakdown["forecast_term"] = entry["forecast_term"]
        # repro-lint: disable=R3 -- only caller (select_node) guards with `if self.recorder:`
        return AdmissionDecision(
            scheduler=entry["scheduler"], workload=entry["workload"],
            qps=entry["qps"], online=entry["online"],
            cpu_demand=entry["cpu_demand"], mem_demand=entry["mem_demand"],
            chosen=entry["chosen"], breakdown=breakdown,
        )


class ICOFScheduler(ICOScheduler):
    """ICO-F: Algorithm 1 scoring on *projected* contention.

    ``intf_h`` gains ``w_f * forecast_drift / OVERFLOW_EDGE`` — the node
    runqlat increase the shared seasonal projection expects ``horizon``
    telemetry windows ahead (``ClusterView.forecast_drift``), normalized
    exactly like every other interference term.  A node whose online fleet
    is heading into its diurnal peak is penalized *now*, at admission,
    instead of becoming the mitigation loop's problem six windows later.

    Fallback is exact: a view without a forecast annotation (no
    ``ForecastService`` attached, or its cadence/trust gates still closed)
    yields ``forecast_drift() is None`` and the score reduces to ICO's
    Eq. (4) term for term; per-node, an untrusted forecast contributes
    zero drift.
    """

    name = "ICO-F"

    def __init__(self, quantifier, config: SchedulerConfig | None = None,
                 w_f: float = 1.0):
        super().__init__(quantifier, config)
        if not w_f > 0.0:
            raise ValueError("w_f must be > 0 (use ICOScheduler to disable)")
        self.w_f = w_f

    def _forecast_term(self, view):
        drift = view.forecast_drift()
        if drift is None:
            return None
        return self.w_f * INTF_NORM * drift
