"""End-to-end scheduler comparison — reproduces Figs. 13-15.

Runs identical pod-arrival traces under ICO / RR / HUP / LQP (plus the
forecast-aware ICO-F when enabled) and reports online avg/p90/p99 response
time plus cross-node CPU/MEM utilization standard deviation.  Every
scheduler consumes the same typed ``repro.cluster.ClusterView`` snapshot
per arrival tick.  ``run_experiment`` optionally runs a
``repro.control.ControlLoop`` between arrivals (mitigation on/off reruns),
optionally threads a shared ``repro.control.ForecastService`` through both
the admission snapshots and the control loop (so placement and mitigation
price contention with one projection), and, per Algorithm 1, queues
rejected pods in a bounded retry queue that is re-offered on subsequent
ticks instead of dropping them permanently.
"""
from __future__ import annotations

import contextlib
import dataclasses
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    ICOFScheduler,
    ICOScheduler,
    InterferenceQuantifier,
    SchedulerConfig,
)
from repro.core.baselines import RoundRobinScheduler, HUPScheduler, LQPScheduler
from repro.core.predictors import RandomForestRegressor
from repro.cluster import workloads as W
from repro.cluster.dataset import generate_latency_dataset, _random_pod
from repro.cluster.simulator import TICKS_PER_DAY, Cluster
from repro.cluster.workloads import Pod
from repro.obs import PhaseTimers, PhaseTimings, RetryDrained, RetryQueued


@dataclasses.dataclass
class ExperimentResult:
    scheduler: str
    avg_rt: float
    p90_rt: float
    p99_rt: float
    cpu_util_std: float
    mem_util_std: float
    placed: int
    rejected: int
    queued_retries: int = 0   # placements that succeeded via the retry queue
    mitigations: int = 0      # control-loop actions applied DURING THIS RUN
    proactive_mitigations: int = 0    # subset planned from forecast drift
    predicted_reduction: float = 0.0  # cost-model claim for this run's actions
    realized_reduction: float = 0.0   # what post-action verification observed
    offers: int = 0           # select_node calls, retries included
    offers_rejected: int = 0  # offers that placed nothing
    # this run's seconds by phase: the loop's rollout/snapshot/verify/
    # forecast/detect/plan and the scheduler's admit.* (see run_experiment);
    # a timing, not a result, so two runs' results compare without it
    phases: dict = dataclasses.field(default_factory=dict, compare=False)


def train_default_predictor(seed: int = 0, num_placements: int = 250):
    """Train the production Random Forest used by Eq. (3)."""
    X, y = generate_latency_dataset(num_placements=num_placements, seed=seed)
    return RandomForestRegressor(n_estimators=30, max_depth=10, seed=seed).fit(X, y)


def make_schedulers(predictor, cfg: SchedulerConfig | None = None,
                    forecast: bool = False):
    """The Figs. 13-15 scheduler set; ``forecast=True`` adds ICO-F.

    ICO-F is opt-in because without a ``ForecastService`` threaded through
    ``run_experiment`` it scores exactly like ICO — running it by default
    would only duplicate ICO's column.
    """
    cfg = cfg or SchedulerConfig()
    q = InterferenceQuantifier(predictor.predict)
    out = {
        "ICO": ICOScheduler(q, cfg),
        "RR": RoundRobinScheduler(cfg),
        "HUP": HUPScheduler(q, cfg),
        "LQP": LQPScheduler(cfg),
    }
    if forecast:
        out["ICO-F"] = ICOFScheduler(q, cfg)
    return out


def _arrival_trace(num_pods: int, seed: int):
    """Pre-generate an identical pod sequence for every scheduler."""
    rng = np.random.default_rng(seed)
    pods, gaps = [], []
    for _ in range(num_pods):
        pods.append(_random_pod(rng))
        gaps.append(int(rng.integers(5, 25)))  # ticks between submissions
    return pods, gaps


def bursty_trace(
    num_online: int = 24,
    num_bursts: int = 5,
    jobs_per_burst: int = 4,
    seed: int = 0,
    burst_gap: tuple = (30, 60),
    job_duration: tuple = (120, 240),
    days: float | None = None,
):
    """Arrival trace for the runtime-mitigation scenario: a stable fleet of
    online services, then recurring waves of heavy short offline jobs.

    Initial placement sees a calm cluster, so any scheduler places the
    online fleet reasonably — the interference only materializes when the
    bursts land, which is exactly the regime a placement-only scheduler
    cannot correct and a runtime control loop can.

    ``burst_gap`` (ticks between waves) and ``job_duration`` stretch the
    trace: the proactive benchmark uses day-scale traces (many waves spread
    over >= TICKS_PER_DAY) so the seasonal forecaster can observe enough of
    the diurnal period to pass its extrapolation-leverage gate.

    ``days`` sizes the trace in diurnal periods directly: ``num_bursts`` is
    raised (never lowered) until the expected arrival span covers
    ``days * TICKS_PER_DAY`` ticks.  The forecaster's leverage gate opens
    after ~0.9 of a period, so its *armed* fraction is roughly
    ``(days - 0.9) / days`` — multi-day traces are what make the proactive
    channel's steady-state value (and ICO-F's admission-time value)
    measurable rather than a tail-end effect.
    """
    rng = np.random.default_rng(seed)
    if days is not None:
        online_span = num_online * 5.0          # mean of the (3, 8) gaps
        per_burst = 2 * (jobs_per_burst - 1) + sum(burst_gap) / 2.0
        num_bursts = max(num_bursts, int(round(
            (days * TICKS_PER_DAY - online_span) / per_burst)))
    pods, gaps = [], []
    for _ in range(num_online):
        name = rng.choice(W.ONLINE_NAMES)
        prof = W.ONLINE_PROFILES[name]
        qps = float(rng.uniform(120, 500))
        pod = Pod(name, qps, True)
        pod.cpu_demand = prof.cpu_per_qps * qps + prof.cpu_base
        pod.mem_demand = prof.mem_per_qps * qps + prof.mem_base
        pods.append(pod)
        gaps.append(int(rng.integers(3, 8)))
    for _ in range(num_bursts):
        for j in range(jobs_per_burst):
            name = rng.choice(W.OFFLINE_NAMES)
            prof = W.OFFLINE_PROFILES[name]
            # mid-size requests: small enough to pass admission on a loaded
            # cluster, bursty enough (burst_range up to 2.1x) to hurt later
            cores = float(prof.cores_choices[-2])
            pod = Pod(name, 0.0, False, duration=int(rng.integers(*job_duration)))
            pod.cpu_demand = cores
            pod.mem_demand = cores * prof.mem_per_core
            pods.append(pod)
            # jobs inside a burst arrive back-to-back; bursts are spread out
            gaps.append(2 if j < jobs_per_burst - 1
                        else int(rng.integers(*burst_gap)))
    return pods, gaps


def run_experiment(
    scheduler,
    pods: list[Pod],
    gaps: list[int],
    num_nodes: int = 12,
    seed: int = 7,
    settle_ticks: int = 40,
    *,
    fleet=None,
    control_loop=None,
    forecast=None,
    control_window: int | None = None,
    retry_limit: int = 8,
    retry_attempts: int = 3,
    recorder=None,
    plan_out: dict | None = None,
) -> ExperimentResult:
    """Replay one arrival trace under a scheduler.

    fleet: optional ``repro.cluster.Fleet``.  When given it defines the
        node population — per-class capacities, delay-curve parameters and
        the rack/zone topology — and ``num_nodes`` is taken from it
        (the explicit argument is ignored, mirroring ``Cluster``).
        ``None`` keeps the legacy homogeneous cluster, and
        is bit-identical to a ``Fleet.homogeneous(num_nodes)`` run.
    control_loop: optional ``repro.control.ControlLoop`` — or a zero-arg
        factory returning one, so drivers sweeping several schedulers can
        thread a *fresh* loop per run instead of sharing one instance.  Its
        ``step`` runs after every rollout window, so mitigation interleaves
        with the same tick cadence the scheduler sees.  Mitigation counters
        in the result are per-run deltas: a reused loop keeps cumulative
        lifetime stats, and reporting those directly would overcount.
    forecast: optional ``repro.control.ForecastService`` (or zero-arg
        factory).  The service observes every telemetry window and
        annotates the admission snapshots with its projection, so a
        forecast-aware scheduler (ICO-F) admits against *projected*
        contention.  Pass the same instance the control loop was built
        with to share one model between placement and mitigation; a
        warm-started service (``load_state_dict``) arrives with its trust
        gate already open.
    control_window: with a control loop or forecast service, slice each
        inter-arrival rollout into windows of at most this many ticks and
        step/observe after every slice.  Day-scale traces have gaps of
        hundreds of ticks; stepping only at arrival boundaries would let
        whole incidents rise and fade between two control iterations, and
        would feed the detector/forecaster telemetry windows of wildly
        uneven length.  Slicing leaves the simulation stream untouched
        (rollout chunks the same ticks identically), so results stay
        comparable with unsliced runs of the same seed.  RT is still
        sampled before every loop step.
    retry_limit / retry_attempts: Algorithm 1 queues a pod when no node is
        feasible; rejected pods are re-offered at each subsequent arrival
        tick, up to ``retry_attempts`` times, from a queue bounded at
        ``retry_limit`` (overflow and exhausted pods count as rejected).
    recorder: optional ``repro.obs.TraceRecorder``.  When given, the run is
        fully traced: the recorder is threaded into the scheduler (admission
        decisions, restored on exit), the control loop and forecast service
        (hotspots, actions, trust-gate flips — unless they already carry
        their own recorder), and the driver itself (window boundaries,
        retry-queue transitions, per-window phase timings).  Tracing only
        observes, between windows; every window runs ``Cluster.rollout``
        with or without it, so the simulated decisions are identical.
    plan_out: optional dict, filled on exit with the run's replayable plan
        (the cluster's mutation log + trace geometry) for
        ``replay_plan_batched`` — the vmapped many-seed re-evaluation of
        this exact placement/action schedule.

    The result's ``phases`` are this run's seconds by phase, each also a
    ``repro.loop.<phase>`` or ``repro.admit.<phase>`` span: ``rollout``
    and ``snapshot`` (the driver's), ``verify``/``forecast``/``detect``/
    ``plan`` (the control loop's) and, for a scheduler with ``timers``,
    ``admit.<phase>`` (its own phases, plus ``place``: ``Cluster.place``).
    ``offers`` counts the scheduler's calls, retries included, and
    ``offers_rejected`` those that placed nothing.
    """
    if control_loop is not None and not hasattr(control_loop, "step"):
        control_loop = control_loop()  # factory -> fresh per-run instance
    if forecast is not None and not hasattr(forecast, "observe"):
        forecast = forecast()          # factory -> fresh per-run instance
    sched_recorder_prev = getattr(scheduler, "recorder", None)
    if recorder is not None:
        if control_loop is not None and control_loop.recorder is None:
            control_loop.recorder = recorder
        if forecast is not None and forecast.recorder is None:
            forecast.recorder = recorder
        if hasattr(scheduler, "recorder"):
            scheduler.recorder = recorder
    # the loop's timers double as the driver's, so rollout and control
    # phases land in one summary; an uncontrolled run gets its own
    timers = (control_loop.timers if control_loop is not None
              else PhaseTimers("repro.loop", jax.profiler.TraceAnnotation))
    admit = getattr(scheduler, "timers", None)
    totals0 = {"": dict(timers.totals),
               "admit.": dict(admit.totals) if admit is not None else {}}
    stats0 = (0, 0, 0.0, 0.0)
    if control_loop is not None:
        s = control_loop.stats
        stats0 = (s.actions_applied, s.proactive_applied,
                  s.predicted_reduction, s.realized_reduction)
    cluster = Cluster(num_nodes=num_nodes, seed=seed, fleet=fleet)
    num_nodes = cluster.n  # fleet overrides the scalar argument
    with timers.phase("rollout"):
        jax.block_until_ready((cluster.rollout(30), cluster.state.cpu_sum))
    if recorder is not None:
        recorder.begin_window(cluster.t)
    rt_all: list[np.ndarray] = []
    cpu_series, mem_series = [], []
    placed = rejected = queued_retries = offers = offers_rejected = 0
    retry_q: deque[tuple[Pod, int]] = deque()  # (pod, attempts so far)
    last_view = None  # advance()'s final window view, reusable at the same t

    def snapshot():
        """One ClusterView per arrival tick: every offer this tick (queued
        re-offers + the new arrival) schedules against the same window,
        annotated with the shared projection when a service is attached.
        Nothing mutates the cluster between advance()'s last window view
        and this snapshot, so a view advance() already built at this t is
        reused instead of recomputing the feature summaries."""
        if last_view is not None and last_view.t == cluster.t:
            view = last_view
        else:
            with timers.phase("snapshot"):
                view = cluster.view()
        if forecast is not None:
            with timers.phase("forecast"):
                forecast.observe(view)  # idempotent if advance() already did
                forecast.annotate(view)
        return view

    def offer(pod: Pod, view, retry: bool = False) -> bool:
        nonlocal offers, offers_rejected
        node = scheduler.select_node(pod, view)
        with (admit.phase("place") if admit is not None
              else contextlib.nullcontext()):
            ok = node >= 0 and cluster.place(pod, node)
        offers += 1
        offers_rejected += int(not ok)
        if recorder is not None:
            # the uid exists only after a successful place; bind it (and the
            # outcome) onto the admission event the scheduler just emitted
            recorder.resolve_admission(uid=pod.uid if ok else -1,
                                       placed=ok, retry=retry)
        return ok

    def drain_retries(view) -> None:
        nonlocal placed, rejected, queued_retries
        for _ in range(len(retry_q)):
            qpod, failed = retry_q.popleft()  # failed = prior re-offers
            if offer(qpod, view, retry=True):
                placed += 1
                queued_retries += 1
                outcome, uid = "placed", qpod.uid
            elif failed + 1 >= retry_attempts:
                rejected += 1
                outcome, uid = "rejected", -1
            else:
                retry_q.append((qpod, failed + 1))
                outcome, uid = "requeued", -1
            if recorder is not None:
                recorder.emit(RetryDrained(
                    workload=qpod.workload, qps=float(qpod.qps),
                    outcome=outcome, uid=uid, attempts=failed + 1))

    def advance(ticks: int, record_util: bool = True) -> None:
        """Roll forward, sampling RT (and stepping the loop) per window.

        Measure BEFORE mitigating: migration frees the source slot, and
        sampling afterwards would silently drop the migrated pod's (worst)
        samples from this window, biasing the mitigation-on distribution.
        The settle phase records RT but not the util series (Figs. 14-15
        average cross-node balance over the arrival phase only).
        """
        nonlocal last_view
        stepped = control_loop is not None or forecast is not None
        while ticks > 0:
            w = ticks
            if stepped and control_window is not None:
                w = min(control_window, ticks)
            t0 = cluster.t
            with timers.phase("rollout"):
                # block on the window outputs INSIDE the timed region: jax
                # dispatch is async, so without this the device compute
                # drains under whatever runs next (the untimed RT-sample
                # conversion, or a later phase) and "rollout" only measures
                # trace/dispatch overhead
                jax.block_until_ready((cluster.rollout(w),
                                       cluster.state.cpu_sum))
            rt_all.append(cluster.online_rt_samples())
            if record_util:
                cpu_series.append(cluster.last["cpu_util"])
                mem_series.append(cluster.last["mem_util"])
            # window boundary: RT already sampled, control not yet stepped —
            # this window's hotspot/action events carry the new index
            if recorder is not None:
                recorder.begin_window(cluster.t)
            if stepped:
                with timers.phase("snapshot"):
                    view = last_view = cluster.view()
                if forecast is not None:
                    forecast.observe(view)
                if control_loop is not None and control_loop.step(
                        cluster, view=view):
                    # mitigation mutated placements: the cached view now
                    # predates them, so the next snapshot must rebuild
                    last_view = None
            tw = timers.pop_window()
            if recorder is not None and tw:
                recorder.emit(PhaseTimings(timings=tw))
            # count the ticks actually simulated: rollout rounds up to CHUNK
            # multiples, and decrementing by the request would re-simulate
            # the rounding overshoot and diverge from an unsliced replay
            progress = int(cluster.t - t0)
            ticks -= progress if progress > 0 else w

    for pod, gap in zip(pods, gaps):
        pod = dataclasses.replace(pod)  # fresh copy per scheduler
        view = snapshot()
        drain_retries(view)
        if offer(pod, view):
            placed += 1
        elif retry_attempts > 0 and len(retry_q) < retry_limit:
            retry_q.append((pod, 0))
            if recorder is not None:
                recorder.emit(RetryQueued(workload=pod.workload,
                                          qps=float(pod.qps), attempts=0))
        else:
            rejected += 1
        advance(gap)

    drain_retries(snapshot())
    rejected += len(retry_q)  # still queued at trace end: never placed
    advance(settle_ticks, record_util=False)
    if recorder is not None and hasattr(scheduler, "recorder"):
        scheduler.recorder = sched_recorder_prev  # schedulers are reused
                                                  # across runs; the trace
                                                  # belongs to this one
    rt = np.concatenate([r for r in rt_all if r.size] or [np.zeros(0)])
    if rt.size == 0:
        rt = np.full(1, np.nan)  # no online pod ever ran
    cpu = np.stack(cpu_series)  # (T, N)
    mem = np.stack(mem_series)
    if control_loop is None:
        mitigations, proactive, predicted, realized = 0, 0, 0.0, 0.0
    else:
        s = control_loop.stats
        mitigations = s.actions_applied - stats0[0]
        proactive = s.proactive_applied - stats0[1]
        predicted = s.predicted_reduction - stats0[2]
        realized = s.realized_reduction - stats0[3]
    phases = {}
    for prefix, t in (("", timers), ("admit.", admit)):
        for k, v in (t.totals.items() if t is not None else ()):
            phases[prefix + k] = v - totals0[prefix].get(k, 0.0)
    if plan_out is not None:
        plan_out.update(
            log=list(cluster.log),
            t_end=float(cluster.t),
            num_nodes=num_nodes,
            seed=seed,
            settle_ticks=settle_ticks,
            fleet=fleet,
        )
    return ExperimentResult(
        scheduler=scheduler.name,
        avg_rt=float(rt.mean()),
        p90_rt=float(np.percentile(rt, 90)),
        p99_rt=float(np.percentile(rt, 99)),
        cpu_util_std=float((100 * cpu).std(axis=1).mean()),
        mem_util_std=float((100 * mem).std(axis=1).mean()),
        placed=placed,
        rejected=rejected,
        queued_retries=queued_retries,
        mitigations=mitigations,
        proactive_mitigations=proactive,
        predicted_reduction=predicted,
        realized_reduction=realized,
        offers=offers,
        offers_rejected=offers_rejected,
        phases=phases,
    )


def replay_timers() -> PhaseTimers:
    """Phase timers for one replay call, each phase a ``repro.replay.*``
    span on the profiler's clock."""
    return PhaseTimers("repro.replay", jax.profiler.TraceAnnotation)


def replay_inputs(plan: dict, sim_seeds, window_ticks: int = 40,
                  bucket: bool = True, timers: PhaseTimers | None = None
                  ) -> dict:
    """The ``state.batched_rollout`` inputs that replay ``plan`` (a
    ``run_experiment`` ``plan_out`` dict, or any dict with ``log``,
    ``t_end``, ``num_nodes`` and optionally ``fleet``) under ``sim_seeds``.

    Returns ``{"state", "profiles", "keys", "events", "fleet"}`` (the
    call's arguments; ``fleet`` is None for the uniform fleet) plus the
    trace geometry ``t_end``, ``num_windows``, ``padded_windows``,
    ``computed_windows`` (the windows that hold a tick before ``t_end``:
    the count ``batched_rollout`` runs) and ``span`` (ticks per window).
    ``keys`` is every seed's chunk key
    stream, (B, padded_windows, cpw, 2), built by two compiled programs
    (``state.seed_keys``, ``state.replay_key_stream``) and bitwise the
    eager per-seed split loop; the stream is prefix-stable, so padding
    windows leaves the real windows' keys as they are.  Its ``plan`` and
    ``keys`` phases are timed on ``timers`` (``replay_timers()`` when none
    is given); ``keys`` waits for the stream.
    """
    from repro.cluster import state as cstate

    if timers is None:
        timers = replay_timers()

    t_end = int(round(plan["t_end"]))
    num_nodes = plan["num_nodes"]
    fleet = plan.get("fleet")
    total_chunks = t_end // cstate.CHUNK
    cpw = max(1, window_ticks // cstate.CHUNK)
    num_windows = -(-total_chunks // cpw)
    # the control step after the run's last window may act at t_end; such
    # an entry changes no simulated tick, and when t_end falls on a window
    # boundary it lies outside the replayed span
    with timers.phase("plan"):
        log = [e for e in plan["log"] if e[1] < t_end]
        events = cstate.extract_plan(log, 0.0, num_windows, cpw,
                                     bucket=bucket)
    padded_windows = events["op"].shape[0]
    span = cpw * cstate.CHUNK
    with timers.phase("keys"):
        keys = cstate.replay_key_stream(cstate.seed_keys(sim_seeds),
                                        padded_windows, cpw)
        keys.block_until_ready()
    if fleet is not None:
        state0 = cstate.ClusterState.create(
            num_nodes, fleet.cores(), fleet.mem_gb())
        fleet_params = fleet.params()
    else:
        state0 = cstate.ClusterState.create(num_nodes)
        fleet_params = None  # batched_rollout defaults to uniform params
    profiles = {k: jnp.asarray(v) for k, v in W.online_arrays().items()}
    return {"state": state0, "profiles": profiles, "keys": keys,
            "events": events, "fleet": fleet_params, "t_end": t_end,
            "num_windows": num_windows, "padded_windows": padded_windows,
            "computed_windows": min(padded_windows, -(-t_end // span)),
            "span": span}


# np.percentile's default ("linear") method on float32 samples, as numpy 2
# computes it: q = p / float32(100), h = (n - 1) * q, then a float32 lerp
# between the order statistics at floor(h) and floor(h) + 1 (both clipped
# to n - 1), from the lower one while the weight is below 0.5 and from the
# upper one after
_RT_Q = np.asarray((90, 99), np.float32) / np.float32(100)
_WARMUP_TICKS = 30     # the RT pool and the utilization windows start here
_INF_BITS = 0x7F800000  # +inf's bits: positive float32 values order as
                        # their int32 bit patterns, all at or below these


def _order_stats(bits, ranks):
    """The ``ranks``-th smallest (0-based) sample of each row, exactly.

    ``bits`` (B, ...) int32 holds the bit patterns of positive float32
    samples, an excluded entry holding INT32_MAX; ``ranks`` (B, K) int32
    lie below each row's sample count.  A bisection on the bit patterns:
    each of its 31 passes halves every rank's interval, counting all K
    ranks of a row in one pass over it (31 halvings close an interval
    shorter than 2**31).  Returns (B, K) float32.
    """
    axes = tuple(range(1, bits.ndim))
    row = (slice(None),) + (None,) * len(axes)

    def halve(_, bounds):
        lo, hi = bounds   # count(bits <= lo) <= rank < count(bits <= hi)
        mid = lo + ((hi - lo) >> 1)
        count = jnp.stack(
            [jnp.sum(bits <= mid[:, k][row], axis=axes, dtype=jnp.int32)
             for k in range(ranks.shape[1])], axis=1)
        past = count > ranks
        return jnp.where(past, lo, mid), jnp.where(past, mid, hi)

    bounds = (jnp.zeros_like(ranks), jnp.full_like(ranks, _INF_BITS))
    _, hi = jax.lax.fori_loop(0, 31, halve, bounds)
    return jax.lax.bitcast_convert_type(hi, jnp.float32)


def _pooled_rt(rt, valid):
    """Per-seed count, mean and p90/p99 of the samples of ``rt`` (B, W,
    span, N, S) that lie on a ``valid`` (W, span) tick and are above 0;
    NaN statistics for a seed with none."""
    keep = valid[None, :, :, None, None] & (rt > 0)
    axes = (1, 2, 3, 4)
    n = jnp.sum(keep, axis=axes, dtype=jnp.int32)
    avg = jnp.sum(jnp.where(keep, rt, 0.0), axis=axes) / n
    bits = jnp.where(keep, jax.lax.bitcast_convert_type(rt, jnp.int32),
                     jnp.iinfo(jnp.int32).max)
    h = (n - 1).astype(jnp.float32)[:, None] * _RT_Q         # (B, 2)
    lo = jnp.clip(jnp.floor(h).astype(jnp.int32), 0, (n - 1)[:, None])
    hi = jnp.minimum(lo + 1, (n - 1)[:, None])
    x = _order_stats(bits, jnp.concatenate([lo, hi], axis=1))
    a, b = x[:, :2], x[:, 2:]
    t = h - jnp.floor(h)
    diff = b - a
    pct = jnp.where(t >= 0.5, b - diff * (1 - t), a + diff * t)
    pct = jnp.where((n > 0)[:, None], pct, jnp.nan)
    return {"count": n, "avg_rt": avg, "p90_rt": pct[:, 0],
            "p99_rt": pct[:, 1]}


@jax.jit
def _replay_stats(rt, cpu_util, mem_util, hot, t_end, settle_ticks,
                  num_windows):
    """The per-seed answers of a replay, from ``batched_rollout``'s
    outputs where they lie: ``_pooled_rt`` over the ticks in
    [30, ``t_end``), the mean over the arrival-phase windows of the
    cross-node CPU/memory utilization std (every window below
    ``num_windows`` for a trace too short to have one), and the windows
    below ``num_windows`` in which the detector flagged a node.  Every
    reduction is per seed, so a seed-sharded input stays sharded.
    Returns a dict of (B,) arrays."""
    padded_windows, span = rt.shape[1], rt.shape[2]
    w_start = jnp.arange(padded_windows) * span
    tick = w_start[:, None] + jnp.arange(span)[None, :]
    pooled = _pooled_rt(rt, (tick >= _WARMUP_TICKS) & (tick < t_end))
    # only the real prefix counts: the engine runs no tick of a padded
    # window, whose outputs are zeros
    real = jnp.arange(padded_windows) < num_windows
    wins = ((w_start >= _WARMUP_TICKS)
            & (w_start + span <= t_end - settle_ticks))
    wins = wins | (real & ~jnp.any(wins))

    def util_std(util):
        std = jnp.std(100 * util, axis=-1)                    # (B, W)
        return jnp.sum(jnp.where(wins, std, 0.0), axis=1) / jnp.sum(wins)

    return dict(pooled, cpu_util_std=util_std(cpu_util),
                mem_util_std=util_std(mem_util),
                hot_windows=jnp.sum(jnp.any(hot, -1) & real, axis=1,
                                    dtype=jnp.int32))


def replay_plan_batched(
    plan: dict,
    sim_seeds=tuple(range(20)),
    window_ticks: int = 40,
    bucket: bool = True,
    devices: int = None,
) -> dict:
    """Re-evaluate one run's placement/action plan under many sim seeds.

    ``plan`` is the ``plan_out`` dict of a ``run_experiment`` call: the
    mutation log plus trace geometry.  The plan is replayed verbatim —
    identical placements, migrations, evictions and resizes at identical
    times — against ``len(sim_seeds)`` independent telemetry streams in ONE
    vmapped ``state.batched_rollout`` call (common-random-placements
    design: the seed axis isolates simulation noise from placement
    quality).  A seed equal to the reference run's reproduces its exact
    key stream, so that entry doubles as a parity check.  A plan recorded
    from a fleet run carries its ``Fleet``; the replay rebuilds the same
    per-node capacities and delay-curve parameters from it.

    ``bucket=True`` (default) pads the event plan to its power-of-two size
    class (``extract_plan(..., bucket=True)``) so every same-class plan in
    a scenario suite reuses ONE compiled executable.  The engine is told
    how many windows are real (``computed_windows``, traced data, so the
    size class still compiles once) and runs no tick of the padded ones
    past ``t_end``, so the numbers are bitwise those of the unbucketed
    replay.  ``devices=N`` shards the seed axis across host devices
    (``state.batched_rollout``'s shard_map path).

    Returns ``{"seeds": [...], "wall_s": float, "phases": dict,
    "num_windows": int, "padded_windows": int, "computed_windows": int}``
    (the plan's windows, the compiled size class, and the windows the
    engine ran: ``padded_windows - computed_windows`` were skipped); each
    per-seed entry carries avg/p90/p99 RT, arrival-phase cross-node
    cpu/mem util std (window-level, so not directly comparable with the
    reference's variable-length control windows), and the folded
    detector's hot-window count.  Warmup ticks (< 30) and any tick past
    ``t_end`` are excluded from the RT pool,
    matching the reference driver's sampling span.  The statistics are
    computed where the series lie (``_replay_stats``, one compiled program
    of exact masked order statistics), so only the per-seed numbers come
    back to the host.

    ``phases`` holds the call's seconds by phase, each also a span
    ``repro.replay.<phase>`` in a profile of the call: ``call`` (all of
    it), ``inputs`` (``replay_inputs``: ``plan``, the log filter and
    ``extract_plan``, then ``keys``, the compiled key stream), ``engine``
    (``batched_rollout`` until its outputs are ready on the device) and
    ``reduce`` (``_replay_stats`` and the one transfer of its per-seed
    numbers).  ``wall_s`` is ``phases["engine"]``.
    """
    from repro.cluster import state as cstate

    timers = replay_timers()
    with timers.phase("call"):
        with timers.phase("inputs"):
            inp = replay_inputs(plan, sim_seeds, window_ticks, bucket,
                                timers=timers)
        t_end, settle_ticks = inp["t_end"], plan.get("settle_ticks", 40)
        num_windows, padded_windows = inp["num_windows"], inp["padded_windows"]

        with timers.phase("engine"):
            _, outs = cstate.batched_rollout(
                inp["state"], inp["profiles"], 0.0, inp["keys"],
                inp["events"], fleet=inp["fleet"], devices=devices,
                num_windows=inp["computed_windows"])
            outs = jax.block_until_ready(outs)

        with timers.phase("reduce"):
            stats = jax.device_get(_replay_stats(
                outs["rt"], outs["cpu_util"], outs["mem_util"], outs["hot"],
                np.int32(t_end), np.int32(settle_ticks),
                np.int32(num_windows)))
            seeds_out = [{
                "sim_seed": int(s),
                **{k: float(stats[k][i]) for k in (
                    "avg_rt", "p90_rt", "p99_rt", "cpu_util_std",
                    "mem_util_std")},
                "hot_windows": int(stats["hot_windows"][i]),
            } for i, s in enumerate(sim_seeds)]
    phases = timers.pop_window()
    return {"seeds": seeds_out, "wall_s": phases["engine"], "phases": phases,
            "num_windows": num_windows, "padded_windows": padded_windows,
            "computed_windows": inp["computed_windows"]}


def compare_schedulers(
    num_pods: int = 60,
    num_nodes: int = 12,
    seed: int = 7,
    predictor=None,
    control: bool = False,
    control_config=None,
    proactive: bool = False,
    forecast: bool = False,
    trace: tuple | None = None,
    control_window: int | None = None,
    fleet=None,
) -> dict[str, ExperimentResult]:
    """Figs. 13-15 comparison across ICO / RR / HUP / LQP (+ ICO-F).

    control=True pairs EVERY scheduler with its own fresh
    ``repro.control.ControlLoop`` (built per run from the shared predictor;
    never a shared instance, so detector state, cooldowns, and learned
    corrections cannot leak across schedulers).  Each scheduler gets its
    *tuned* profile via ``scheduler_loop_config`` — the guards that win for
    ICO hurt RR/HUP placements — unless ``control_config`` pins one shared
    config explicitly.  ``proactive=True`` additionally switches on the
    forecast channel (ahead-of-time mitigation).  ``forecast=True`` adds
    the ICO-F scheduler and threads one fresh ``ForecastService`` per run
    through BOTH the admission snapshots and (when control is on) that
    run's control loop, so placement and mitigation consume the same
    projection.  ``trace`` optionally replaces the default arrival trace
    with a pre-built (pods, gaps) pair, e.g. ``bursty_trace(...)``;
    ``control_window`` and ``fleet`` are forwarded to ``run_experiment``
    (day-scale traces need the gap slicing; a ``repro.cluster.Fleet``
    swaps in a heterogeneous node population for every scheduler alike).
    """
    predictor = predictor or train_default_predictor(seed=seed)
    pods, gaps = trace if trace is not None else _arrival_trace(num_pods, seed)
    out = {}
    for name, sched in make_schedulers(predictor, forecast=forecast).items():
        cfg = None
        if control:
            from repro.control import scheduler_loop_config  # deferred, below

            cfg = (control_config if control_config is not None
                   else scheduler_loop_config(name, proactive=proactive))
        svc = None
        # a service only where something consumes it: ICO-F admission, or a
        # proactive loop sharing the projection — threading one through the
        # other runs would pay per-window forecaster updates for nothing
        if forecast and (name == "ICO-F" or (control and proactive)):
            from repro.control import ForecastService

            # built from the loop profile so the shared instance carries the
            # SAME gates/horizon the loop's own config asks for (an external
            # service's config wins inside the loop)
            svc = (ForecastService(cfg.forecast, cfg.horizon)
                   if cfg is not None else ForecastService())
        loop = None
        if control:
            from repro.control import ControlLoop  # deferred: optional dep

            loop = lambda cfg=cfg, svc=svc: ControlLoop(  # noqa: E731
                InterferenceQuantifier(predictor.predict), cfg,
                forecast_service=svc)
        out[name] = run_experiment(sched, pods, gaps, num_nodes=num_nodes,
                                   seed=seed, fleet=fleet, control_loop=loop,
                                   forecast=svc,
                                   control_window=control_window)
    return out
