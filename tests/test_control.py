"""Runtime mitigation control plane: detector (node + per-slot attribution),
actions, policy, simulator primitives (migrate/resize/reconcile), retry
queue, the closed loop, and post-action verification/calibration."""
import dataclasses

import numpy as np
import pytest

from repro.cluster.experiment import bursty_trace, compare_schedulers, run_experiment
from repro.cluster.simulator import S_OFF, S_ON, Cluster
from repro.cluster.workloads import OFFLINE_PROFILES, ONLINE_PROFILES, Pod
from repro.control import (
    ControlLoop,
    ControlLoopConfig,
    DetectorConfig,
    EvictOffline,
    MitigationPolicy,
    PolicyConfig,
    ScaleOut,
    StreamingDetector,
    VerticalResize,
    scheduler_loop_config,
)
from repro.core import metric
from repro.core.interference import InterferenceQuantifier
from repro.core.scheduler import ICOScheduler


def _hists(n_nodes, level, rng):
    """Per-node histograms of gamma samples with the given mean level."""
    samples = rng.gamma(2.0, np.asarray(level)[:, None] / 2.0, (n_nodes, 64))
    return np.stack([np.histogram(s, bins=200, range=(0, 1000))[0] for s in samples])


def _cheap_quantifier():
    # predicted pod runqlat := node's current runqlat_avg feature
    return InterferenceQuantifier(lambda X: X[:, 21])


def _online_pod(qps=300.0, name="web_search"):
    p = Pod(name, qps, True)
    p.cpu_demand, p.mem_demand = 0.022 * qps + 0.8, 0.011 * qps + 2.0
    return p


def _offline_pod(cores=12.0, duration=500, name="graph_analytics"):
    p = Pod(name, 0.0, False, duration=duration)
    p.cpu_demand = cores
    p.mem_demand = cores * OFFLINE_PROFILES[name].mem_per_core
    return p


# ---------------- detector ----------------

def test_detector_flags_step_in_runqlat():
    rng = np.random.default_rng(0)
    det = StreamingDetector(4, DetectorConfig())
    steady = [20.0, 25.0, 15.0, 22.0]
    for _ in range(6):
        hot = det.update(_hists(4, steady, rng))
        assert not hot.any()          # steady load never flags
    stepped = [20.0, 600.0, 15.0, 22.0]  # node 1 drifts hard
    flagged = np.zeros(4, bool)
    for _ in range(4):
        flagged |= det.update(_hists(4, stepped, rng))
    assert flagged[1]
    assert not flagged[[0, 2, 3]].any()  # only the stepped node


def test_detector_single_jitted_call_tracks_quantiles():
    rng = np.random.default_rng(1)
    det = StreamingDetector(3)
    det.update(_hists(3, [50.0, 200.0, 10.0], rng))
    diag = det.last_diag
    # decayed quantile estimates order with the underlying load
    assert diag["p_tail"][1] > diag["p_tail"][0] > diag["p_tail"][2]
    assert diag["avg"].shape == (3,)


def test_detector_warmup_consumes_cusum():
    """Regression: drift accumulated during the warmup transient used to be
    suppressed but not consumed, firing a spurious flag at steps == warmup."""
    rng = np.random.default_rng(3)
    cfg = DetectorConfig(warmup=3, abs_threshold=1e9)  # isolate the drift path
    det = StreamingDetector(1, cfg)
    det.update(_hists(1, [20.0], rng))   # seeds the baseline
    det.update(_hists(1, [120.0], rng))  # warmup transient drifts hard...
    det.update(_hists(1, [120.0], rng))  # ...past drift_threshold
    # back at baseline exactly when warmup expires: the transient's leftover
    # CUSUM must not fire now (raw flags consumed it during warmup)
    for _ in range(3):
        assert not det.update(_hists(1, [20.0], rng)).any()


def _slot_hists(levels, rng):
    """(N, S) mean levels -> (N, S, 200) per-slot histograms."""
    return np.stack([_hists(len(row), row, rng) for row in levels])


def test_detector_per_slot_attribution():
    """A hotspot flag carries the (node, slot) whose runqlat drifted."""
    rng = np.random.default_rng(7)
    det = StreamingDetector(2)
    calm = [[30.0, 30.0, 0.0], [25.0, 25.0, 0.0]]
    for _ in range(5):
        assert not det.update(_slot_hists(calm, rng)).any()
    # a heavy job "lands" in slot 2 of node 0 and drags the node up
    hot_lv = [[80.0, 80.0, 600.0], [25.0, 25.0, 0.0]]
    flagged = np.zeros(2, bool)
    for _ in range(4):
        hot = det.update(_slot_hists(hot_lv, rng))
        if hot.any():
            assert det.hot_slots() == {0: 2}  # attribution names the arrival
        flagged |= hot
    assert flagged[0] and not flagged[1]
    assert det.slot_scores.shape == (2, 3)
    assert det.slot_scores[0, 2] > det.slot_scores[0, :2].max()


def test_detector_clear_slots_resets_attribution():
    """Regression: a reused slot used to inherit the evicted tenant's drift
    score via decay only; clear_slots keys the track on the tenant."""
    rng = np.random.default_rng(13)
    seq = [_slot_hists([[30.0, 600.0], [25.0, 25.0]], rng) for _ in range(2)]
    calm = _slot_hists([[30.0, 30.0], [25.0, 25.0]], rng)
    cleared, control = StreamingDetector(2), StreamingDetector(2)
    for h in seq:
        cleared.update(h)
        control.update(h)
    assert cleared.slot_scores[0, 1] > cleared.cfg.attribution_floor
    cleared.clear_slots([0], [1])
    assert cleared.slot_scores[0, 1] == 0.0
    cleared.update(calm)
    control.update(calm)
    # without the clear the new tenant still carries half the old score;
    # with it the slot only scores its own (modest) arrival jump
    assert cleared.slot_scores[0, 1] < 0.5 * control.slot_scores[0, 1]


def test_loop_resets_attribution_on_slot_reuse():
    """The ControlLoop diffs slot_uids() and clears the detector track when
    the simulator places/migrates/evicts into a slot."""
    c = Cluster(num_nodes=2, seed=0)
    heavy = _offline_pod(14.0, duration=2000)
    assert c.place(heavy, 0)
    # budget 0: the loop observes and attributes but never mutates the pods
    loop = ControlLoop(_cheap_quantifier(),
                       ControlLoopConfig(policy=PolicyConfig(budget=0.0)))
    c.rollout(10)
    loop.step(c)
    _, node, slot = c._pod_slots[heavy.uid]
    s_idx = S_ON + slot
    score_heavy = float(loop.detector.slot_scores[node, s_idx])
    assert score_heavy > 20  # the landing jump was scored

    c.remove(heavy.uid)
    tiny = _offline_pod(2.0, duration=2000)
    assert c.place(tiny, 0)
    assert c._pod_slots[tiny.uid] == (("off", node, slot))  # slot reused
    c.rollout(10)
    loop.step(c)
    # decay alone would leave ~half the heavy tenant's score on the slot;
    # the tenant-keyed clear leaves only the tiny pod's own small jump
    assert float(loop.detector.slot_scores[node, s_idx]) < 0.3 * score_heavy


def test_hot_slots_returns_no_attribution_below_score_floor():
    """Regression: an acute p-tail flag with zero drift used to argmax over
    all-zero scores and silently blame slot 0."""
    det = StreamingDetector(1, DetectorConfig(abs_threshold=300.0))
    hists = np.zeros((1, 2, metric.NUM_BINS), np.float32)
    hists[0, 0, 120] = 64.0  # steady 600: acute tail, no drift to score
    flagged = False
    for _ in range(12):
        flagged |= bool(det.update(hists).any())
    assert flagged and det.last_hot.any()
    # steady state: every slot score has decayed to ~0
    assert det.slot_scores.max() < det.cfg.attribution_floor
    assert det.hot_slots() == {}                    # no argmax-of-noise
    assert not det.attribution().any()              # policy falls back too


def test_detector_determinism_across_reset():
    rng = np.random.default_rng(11)
    seq = [_slot_hists([[20.0, 0.0], [30.0, 400.0]], rng) for _ in range(6)]
    det = StreamingDetector(2)
    first = [(det.update(h).copy(), det.slot_scores.copy()) for h in seq]
    det.reset()
    second = [(det.update(h).copy(), det.slot_scores.copy()) for h in seq]
    for (h1, s1), (h2, s2) in zip(first, second):
        np.testing.assert_array_equal(h1, h2)
        np.testing.assert_allclose(s1, s2)


# ---------------- simulator primitives ----------------

def test_migrate_preserves_state_invariants():
    c = Cluster(num_nodes=3, seed=0)
    on, off = _online_pod(400.0), _offline_pod(8.0)
    assert c.place(on, 0) and c.place(off, 0)
    before = c.active_pod_count()

    assert c.migrate(on.uid, 1)
    assert c.active_pod_count() == before  # conserved
    assert c._pod_slots[on.uid][1] == 1
    assert not np.asarray(c.state["on_active"])[0].any()  # src slot freed
    dst_slot = c._pod_slots[on.uid][2]
    assert float(c.state["on_qps_mean"][1, dst_slot]) == 400.0

    assert c.migrate(off.uid, 2)
    assert c.active_pod_count() == before
    assert float(np.asarray(c.state["off_cores"])[0].sum()) == 0.0  # no stale src
    assert float(np.asarray(c.state["off_cores"])[2].sum()) == 8.0

    with pytest.raises(KeyError):
        c.migrate(999, 1)


def test_migrate_full_destination_is_noop():
    c = Cluster(num_nodes=2, seed=0)
    from repro.cluster.simulator import S_ON
    for _ in range(S_ON):
        assert c.place(_online_pod(100.0), 1)
    p = _online_pod(200.0)
    assert c.place(p, 0)
    before = c.active_pod_count()
    assert not c.migrate(p.uid, 1)          # node 1 has no free slot
    assert c._pod_slots[p.uid][1] == 0      # state untouched
    assert c.active_pod_count() == before


def test_resize_conserves_offline_work():
    c = Cluster(num_nodes=1, seed=0)
    off = _offline_pod(12.0, duration=400)
    assert c.place(off, 0)
    _, n, s = c._pod_slots[off.uid]
    mem0 = float(c.state["off_mem"][n, s])
    assert c.resize(off.uid, cores=6.0)
    assert float(c.state["off_cores"][n, s]) == pytest.approx(6.0)
    assert float(c.state["off_mem"][n, s]) == pytest.approx(mem0 / 2)
    assert int(c.state["off_remaining"][n, s]) == 800  # half cores, double time

    on = _online_pod(300.0)
    assert c.place(on, 0)
    assert c.resize(on.uid, qps=150.0)
    _, n, s = c._pod_slots[on.uid]
    assert float(c.state["on_qps_mean"][n, s]) == 150.0


def test_reconcile_clears_finished_offline_jobs():
    c = Cluster(num_nodes=1, seed=0)
    off = _offline_pod(8.0, duration=5)
    assert c.place(off, 0)
    c.rollout(10)  # job finishes inside; rollout reconciles
    assert off.uid not in c._pod_slots
    assert float(np.asarray(c.state["off_cores"]).sum()) == 0.0
    with pytest.raises(KeyError, match="unknown pod uid"):
        c.remove(off.uid)


# ---------------- actions & policy ----------------

def test_policy_respects_budget_and_ranks_by_net_gain():
    c = Cluster(num_nodes=4, seed=0)
    for _ in range(3):
        assert c.place(_offline_pod(12.0), 0)
    assert c.place(_online_pod(500.0), 0)
    c.rollout(10)
    cfg = PolicyConfig(budget=10.0, max_actions_per_node=4)
    policy = MitigationPolicy(_cheap_quantifier(), cfg)
    hot = np.array([True, False, False, False])
    plan = policy.plan(c, c.view(), hot)
    assert plan  # an overloaded node yields candidates
    assert sum(a.cost for a in plan) <= cfg.budget
    net = [a.predicted_reduction - cfg.cost_weight * a.cost for a in plan]
    assert all(g > 0 for g in net)
    assert net == sorted(net, reverse=True)  # greedy order
    assert all(a.node == 0 for a in plan)


def test_action_cost_accounting():
    cfg = PolicyConfig()
    c = Cluster(num_nodes=2, seed=0)
    off = _offline_pod(10.0, duration=100)
    assert c.place(off, 0)
    c.rollout(10)
    policy = MitigationPolicy(_cheap_quantifier(), cfg)
    plan = policy._candidates(c, c.view(), 0, np.array([True, False]))
    evict = next(a for a in plan if isinstance(a, EvictOffline))
    assert evict.cost == pytest.approx(cfg.evict_cost_per_core * 10.0)
    resize = next(a for a in plan if isinstance(a, VerticalResize))
    # cgroup write + stretch penalty: halving cores doubles remaining ticks
    remaining = c.pods_on_node(0)[0]["remaining"]
    stretch = remaining * (1.0 / cfg.throttle_frac - 1.0)
    assert resize.cost == pytest.approx(cfg.resize_cost + 0.002 * stretch)
    assert resize.new_cores == pytest.approx(10.0 * cfg.throttle_frac)


def test_evict_applies_and_tolerates_missing_pod():
    c = Cluster(num_nodes=1, seed=0)
    off = _offline_pod(8.0)
    assert c.place(off, 0)
    act = EvictOffline(node=0, uid=off.uid, cost=1.0, predicted_reduction=5.0)
    assert act.apply(c)
    assert not act.apply(c)  # already gone: no-op, not an error


def test_scale_out_rolls_back_replica_when_original_vanished():
    c = Cluster(num_nodes=2, seed=0)
    on = _online_pod(400.0)
    assert c.place(on, 0)
    act = ScaleOut(node=0, uid=on.uid, workload="web_search", dst=1,
                   replica_qps=200.0)
    c.remove(on.uid)  # original disappears between planning and acting
    before = c.active_pod_count()
    assert not act.apply(c)
    assert c.active_pod_count() == before  # the replica was rolled back
    assert not np.asarray(c.state["on_active"])[1].any()


def test_planned_actions_tolerate_job_finishing_before_apply():
    """reconcile() runs inside resize/remove: a plan computed against a job
    that finishes before acting degrades to a no-op, not an error."""
    c = Cluster(num_nodes=2, seed=0)
    off = _offline_pod(12.0, duration=5)
    assert c.place(off, 0)
    resize = VerticalResize(node=0, uid=off.uid, new_cores=6.0)
    evict = EvictOffline(node=0, uid=off.uid)
    c.rollout(10)  # the job finishes mid-plan; rollout reconciles it away
    assert not resize.apply(c)
    assert not evict.apply(c)


def test_scale_out_relief_charges_replica_base_on_destination():
    """Splitting QPS keeps cpu_base on the source AND adds a new cpu_base on
    the destination; the relief estimate must charge that added load."""
    c = Cluster(num_nodes=3, seed=0)
    assert c.place(_online_pod(900.0), 0)
    for _ in range(3):
        assert c.place(_offline_pod(12.0), 0)
    c.rollout(10)
    policy = MitigationPolicy(_cheap_quantifier())
    data = c.view()
    cands = policy._candidates(c, data, 0, np.array([True, False, False]))
    so = [a for a in cands if isinstance(a, ScaleOut)]
    assert so
    a = so[0]
    prof = ONLINE_PROFILES["web_search"]
    rho_p = policy._pressure(c, data, 0, c.pods_on_node(0))
    cores = float(data.cpu_sum[0])
    pred = np.asarray(policy.q.intf_pod(900.0, data.features)) * metric.OVERFLOW_EDGE
    cpu_half = prof.cpu_per_qps * 450.0
    legacy = (policy._relief(rho_p, cpu_half, cores)
              + 0.3 * max(float(pred[0] - pred[a.dst]), 0.0))
    dst_cores = float(data.cpu_sum[a.dst])
    dst_add = cpu_half + prof.cpu_base
    penalty = policy._relief(
        float(data.cpu_cur[a.dst]) / dst_cores + dst_add / dst_cores,
        dst_add, dst_cores)
    assert penalty > 0
    assert a.predicted_reduction == pytest.approx(legacy - penalty)


def test_vertical_resize_respects_min_cores_floor():
    cfg = PolicyConfig(min_offline_cores=4.0)
    policy = MitigationPolicy(_cheap_quantifier(), cfg)
    c = Cluster(num_nodes=2, seed=0)
    small = _offline_pod(6.0)   # 6 * 0.5 = 3 < 4: would shrink past the floor
    big = _offline_pod(12.0)    # 12 * 0.5 = 6 >= 4: still throttleable
    assert c.place(small, 0) and c.place(big, 0)
    c.rollout(10)
    cands = policy._candidates(c, c.view(), 0, np.array([True, False]))
    resized = {a.uid for a in cands if isinstance(a, VerticalResize)}
    assert big.uid in resized
    assert small.uid not in resized  # no unbounded re-throttling toward zero
    # eviction of the small job is still on the table
    assert small.uid in {a.uid for a in cands if isinstance(a, EvictOffline)}


def test_policy_attribution_overrides_heuristics():
    """With per-slot drift scores, the drifted pod is the victim even when
    the heaviest-pressure / highest-QPS heuristics point elsewhere."""
    c = Cluster(num_nodes=2, seed=0)
    heavy = _offline_pod(12.0)   # pressure heuristic's pick
    light = _offline_pod(4.0)    # attribution's pick
    hi_qps = _online_pod(500.0)  # QPS heuristic's pick
    lo_qps = _online_pod(300.0)  # attribution's pick
    for p in (heavy, light, hi_qps, lo_qps):
        assert c.place(p, 0)
    c.rollout(10)
    policy = MitigationPolicy(_cheap_quantifier())
    data = c.view()
    hot = np.array([True, False])
    slots = {uid: c._pod_slots[uid][2] for uid in
             (heavy.uid, light.uid, hi_qps.uid, lo_qps.uid)}
    attribution = np.zeros((2, S_ON + S_OFF))
    attribution[0, S_ON + slots[light.uid]] = 50.0  # light job drifted
    attribution[0, slots[lo_qps.uid]] = 50.0        # low-QPS service drifted

    base = policy._candidates(c, data, 0, hot)
    attr = policy._candidates(c, data, 0, hot, attribution=attribution)
    first_off = lambda cands: next(a.uid for a in cands
                                   if isinstance(a, EvictOffline))
    victim = lambda cands: next(a.uid for a in cands if isinstance(a, ScaleOut))
    assert first_off(base) == heavy.uid and victim(base) == hi_qps.uid
    assert first_off(attr) == light.uid and victim(attr) == lo_qps.uid


def test_plan_corrections_demote_action_kind():
    c = Cluster(num_nodes=4, seed=0)
    for _ in range(3):
        assert c.place(_offline_pod(12.0), 0)
    c.rollout(10)
    policy = MitigationPolicy(_cheap_quantifier(),
                              PolicyConfig(budget=10.0, max_actions_per_node=4))
    hot = np.array([True, False, False, False])
    data = c.view()
    base = policy.plan(c, data, hot)
    assert any(isinstance(a, EvictOffline) for a in base)
    demoted = policy.plan(c, data, hot, corrections={"evict_offline": 0.0})
    assert not any(isinstance(a, EvictOffline) for a in demoted)


# ---------------- retry queue ----------------

class _FlakyScheduler:
    """Rejects the first k offers, then always picks node 0."""

    name = "flaky"

    def __init__(self, k):
        self.k = k
        self.calls = 0

    def select_node(self, pod, data):
        self.calls += 1
        return -1 if self.calls <= self.k else 0


def test_retry_queue_reoffers_rejected_pods():
    pods = [_online_pod(100.0) for _ in range(4)]
    gaps = [3, 3, 3, 3]
    r = run_experiment(_FlakyScheduler(2), pods, gaps, num_nodes=1, seed=0,
                       settle_ticks=5)
    assert r.queued_retries > 0            # early rejects landed via the queue
    assert r.placed + r.rejected == len(pods)
    assert r.placed == 4                   # nobody permanently dropped


def test_retry_queue_bounded_and_attempts_exhausted():
    pods = [_online_pod(100.0) for _ in range(5)]
    gaps = [2] * 5
    r = run_experiment(_FlakyScheduler(10_000), pods, gaps, num_nodes=1,
                       seed=0, settle_ticks=5, retry_limit=2, retry_attempts=2)
    assert r.placed == 0
    assert r.rejected == 5
    assert r.queued_retries == 0


# ---------------- closed loop ----------------

def test_control_loop_reduces_node_delay_under_overload():
    def overloaded_cluster():
        c = Cluster(num_nodes=4, seed=5)
        assert c.place(_online_pod(400.0), 0)
        for _ in range(3):
            assert c.place(_offline_pod(12.0, duration=2000), 0)
        c.rollout(10)
        return c

    delays = {}
    for control in (False, True):
        c = overloaded_cluster()
        loop = ControlLoop(_cheap_quantifier()) if control else None
        for _ in range(8):
            c.rollout(10)
            if loop is not None:
                loop.step(c)
        delays[control] = float(c.last["delay"].mean())
    assert delays[True] < 0.5 * delays[False]
    assert loop.stats.actions_applied > 0
    assert loop.stats.hotspots_flagged > 0


def test_policy_excludes_recently_acted_pods():
    c = Cluster(num_nodes=2, seed=0)
    off = _offline_pod(12.0)
    assert c.place(off, 0)
    c.rollout(10)
    policy = MitigationPolicy(_cheap_quantifier(), PolicyConfig())
    hot = np.array([True, False])
    assert policy.plan(c, c.view(), hot)  # the job is actionable...
    assert policy.plan(c, c.view(), hot,
                       exclude_uids=frozenset({off.uid})) == []  # ...unless cooling down


def test_loop_uid_cooldown_prevents_ping_pong():
    c = Cluster(num_nodes=2, seed=0)
    off = _offline_pod(12.0, duration=2000)
    assert c.place(off, 0)
    loop = ControlLoop(
        _cheap_quantifier(),
        ControlLoopConfig(cooldown=0, uid_cooldown=100),
    )
    acted_on = []
    for _ in range(6):
        c.rollout(10)
        acted_on += [getattr(a, "uid", -1) for a in loop.step(c)]
    # the job may be hit once (evict or throttle); never repeatedly
    assert acted_on.count(off.uid) <= 1


def test_control_loop_idle_on_calm_cluster():
    c = Cluster(num_nodes=3, seed=2)
    assert c.place(_online_pod(150.0), 0)
    loop = ControlLoop(_cheap_quantifier())
    for _ in range(6):
        c.rollout(10)
        loop.step(c)
    assert loop.stats.actions_applied == 0


def _overloaded_cluster(seed=5, num_nodes=4):
    c = Cluster(num_nodes=num_nodes, seed=seed)
    assert c.place(_online_pod(400.0), 0)
    for _ in range(3):
        assert c.place(_offline_pod(12.0, duration=2000), 0)
    c.rollout(10)
    return c


def test_verification_learns_per_kind_corrections():
    c = _overloaded_cluster()
    loop = ControlLoop(_cheap_quantifier())
    for _ in range(8):
        c.rollout(10)
        loop.step(c)
    s = loop.stats
    assert s.actions_applied > 0
    assert s.actions_verified > 0
    assert s.predicted_reduction > 0
    assert np.isfinite(s.realized_reduction)
    assert s.calibration_error() >= 0
    # at least one applied kind was re-calibrated away from 1.0, within clamps
    assert loop.corrections
    cfg = loop.cfg
    for kind, corr in loop.corrections.items():
        assert cfg.corr_min <= corr <= cfg.corr_max
        assert kind in s.by_kind
    # history carries the realized-vs-predicted record
    verified = [v for h in loop.history for v in h["verified"]]
    assert len(verified) == s.actions_verified
    assert all(np.isfinite(v["realized"]) for v in verified)


def test_verification_discards_qps_renormalised_window():
    """Regression: pod-set diffs miss QPS renormalisation — a scale-out
    halves the source pod's QPS without touching the uid set, so the
    post-action window read as 'clean' while its delta measured the
    renormalisation, not the action.  The signature check must discard it."""
    c = _overloaded_cluster()
    # source-relief only so the online pod stays put and the uid set of the
    # acted node cannot change by itself
    loop = ControlLoop(_cheap_quantifier(), ControlLoopConfig(
        policy=PolicyConfig(destination_actions=False)))
    applied = []
    for _ in range(10):
        c.rollout(10)
        applied = loop.step(c)
        if applied:
            break
    assert applied and loop._to_verify
    node = applied[0].node
    victim = next(p for p in c.pods_on_node(node) if p["kind"] == "on")
    # renormalise the pod's QPS between acting and checking (what a
    # concurrent scale-out does to its source): uid set unchanged
    assert c.resize(victim["uid"], qps=victim["qps"] * 0.5)
    before_discarded = loop.stats.verifications_discarded
    before_verified = loop.stats.actions_verified
    c.rollout(10)
    loop.step(c)
    assert loop.stats.verifications_discarded > before_discarded
    assert loop.stats.actions_verified == before_verified


def test_loop_resets_on_new_cluster_of_same_size():
    """Regression: reusing a loop on a new same-size cluster used to carry
    detector state, cooldown maps, and pending flags silently."""
    loop = ControlLoop(_cheap_quantifier())
    c1 = _overloaded_cluster(seed=5)
    for _ in range(6):
        c1.rollout(10)
        loop.step(c1)
    assert loop.stats.actions_applied > 0
    assert loop._uid_last_acted  # cooldown state from cluster 1
    steps_c1 = int(loop.detector.steps)
    assert steps_c1 > 1

    c2 = Cluster(num_nodes=c1.n, seed=9)  # same size, different cluster
    c2.rollout(10)
    loop.step(c2)
    assert int(loop.detector.steps) == 1  # fresh detector, not c1 leftovers
    assert not loop._uid_last_acted       # stale pod ids dropped
    assert not loop._pending


def test_run_experiment_reports_per_run_mitigation_delta():
    """Regression: a reused loop keeps lifetime stats; each run must report
    its own delta, not the cumulative count."""
    pods, gaps = bursty_trace(num_online=6, num_bursts=2, jobs_per_burst=2, seed=1)
    loop = ControlLoop(_cheap_quantifier())
    r1 = run_experiment(ICOScheduler(_cheap_quantifier()), pods, gaps,
                        num_nodes=6, seed=3, settle_ticks=10, control_loop=loop)
    r2 = run_experiment(ICOScheduler(_cheap_quantifier()), pods, gaps,
                        num_nodes=6, seed=3, settle_ticks=10, control_loop=loop)
    assert r1.mitigations > 0
    assert r1.mitigations + r2.mitigations == loop.stats.actions_applied
    assert (r1.predicted_reduction + r2.predicted_reduction
            == pytest.approx(loop.stats.predicted_reduction))
    assert (r1.realized_reduction + r2.realized_reduction
            == pytest.approx(loop.stats.realized_reduction))


def test_run_experiment_with_control_loop_integration():
    pods, gaps = bursty_trace(num_online=6, num_bursts=2, jobs_per_burst=2, seed=1)
    q = _cheap_quantifier()
    loop = ControlLoop(_cheap_quantifier())
    r = run_experiment(ICOScheduler(q), pods, gaps, num_nodes=6, seed=3,
                       settle_ticks=10, control_loop=loop)
    assert r.mitigations == loop.stats.actions_applied  # fresh loop: delta == lifetime
    assert r.placed + r.rejected == len(pods)
    assert np.isfinite(r.p99_rt)


class _CheapPredictor:
    """Predicted pod runqlat := the node's current runqlat_avg feature."""

    @staticmethod
    def predict(X):
        return X[:, 21]


def test_compare_schedulers_threads_a_loop_per_scheduler():
    pods, gaps = bursty_trace(num_online=5, num_bursts=1, jobs_per_burst=2, seed=1)
    res = compare_schedulers(num_nodes=6, seed=3, predictor=_CheapPredictor(),
                             control=True, trace=(pods, gaps))
    assert set(res) == {"ICO", "RR", "HUP", "LQP"}
    for r in res.values():
        assert np.isfinite(r.p99_rt)
        assert r.mitigations >= 0
        assert np.isfinite(r.predicted_reduction)
        assert np.isfinite(r.realized_reduction)


def test_compare_schedulers_forecast_adds_icof():
    """forecast=True adds the ICO-F column and threads a per-run
    ForecastService; on a short trace the trust gate never opens, so
    ICO-F's run is identical to ICO's (exact fallback, shared pipeline)."""
    from repro.control import scheduler_loop_config

    pods, gaps = bursty_trace(num_online=5, num_bursts=1, jobs_per_burst=2,
                              seed=1)
    res = compare_schedulers(num_nodes=6, seed=3, predictor=_CheapPredictor(),
                             forecast=True, trace=(pods, gaps),
                             control_window=20)
    assert set(res) == {"ICO", "ICO-F", "RR", "HUP", "LQP"}
    assert res["ICO-F"].p99_rt == res["ICO"].p99_rt
    assert res["ICO-F"].placed == res["ICO"].placed
    # ICO-F keeps ICO's aggressive mitigation profile
    assert scheduler_loop_config("ICO-F").policy.destination_actions


class _StuckCluster:
    """rollout() that never advances the clock (bad chunk rounding)."""

    CHUNK = 10
    n = 2
    t = 0.0

    def rollout(self, k):
        pass


def test_run_raises_on_zero_rollout_progress():
    """Regression: ControlLoop.run used to spin forever when a rollout
    advanced the simulator clock by zero ticks."""
    loop = ControlLoop(_cheap_quantifier())
    with pytest.raises(RuntimeError, match="no progress"):
        loop.run(_StuckCluster(), num_ticks=30)


def test_loop_proactive_smoke_and_stats():
    """proactive=True activates the forecast channel without breaking the
    reactive path; counters and calibration stay finite."""
    c = _overloaded_cluster()
    loop = ControlLoop(_cheap_quantifier(), ControlLoopConfig(proactive=True))
    for _ in range(8):
        c.rollout(10)
        loop.step(c)
    s = loop.stats
    assert s.actions_applied > 0          # reactive mitigation still works
    assert s.proactive_applied >= 0
    assert s.proactive_applied <= s.actions_applied
    assert loop.forecaster is not None    # the channel observed QPS
    assert loop.forecaster.last_pred is not None
    # calibration is NaN when every pod's slot churned before maturing
    # (mitigation moves the victims, which clears their fits) — finite
    # otherwise; either way it must not blow up
    cal = loop.forecaster.calibration_error()
    assert np.isnan(cal) or cal >= 0
    for h in loop.history:
        assert "proactive_nodes" in h


def test_run_experiment_threads_proactive_counters():
    pods, gaps = bursty_trace(num_online=5, num_bursts=1, jobs_per_burst=2,
                              seed=1)
    loop = ControlLoop(_cheap_quantifier(), ControlLoopConfig(proactive=True))
    r = run_experiment(ICOScheduler(_cheap_quantifier()), pods, gaps,
                       num_nodes=6, seed=3, settle_ticks=10,
                       control_loop=loop, control_window=20)
    assert r.proactive_mitigations == loop.stats.proactive_applied
    assert r.proactive_mitigations <= r.mitigations
    assert np.isfinite(r.p99_rt)


def test_scheduler_profiles_and_proactive_toggle():
    ico = scheduler_loop_config("ICO")
    rr = scheduler_loop_config("RR")
    hup = scheduler_loop_config("HUP")
    # RR/HUP get the conservative source-relief-only profile: mitigation
    # tuned for ICO placements hurt them on some seeds (PR 2 grid), and
    # destination-gambling actions were the churn driver
    assert ico.policy.destination_actions
    for cfg in (rr, hup):
        assert not cfg.policy.destination_actions
        assert cfg.policy.budget < ico.policy.budget
        assert cfg.uid_cooldown > ico.uid_cooldown
        assert cfg.detector.drift_threshold > ico.detector.drift_threshold
    assert not ico.proactive
    assert scheduler_loop_config("HUP", proactive=True).proactive
    assert scheduler_loop_config("unknown") == ControlLoopConfig()


def test_core_reexports_control_api():
    import repro.core as core

    assert core.ControlLoop is ControlLoop
    assert core.ControlLoopConfig is ControlLoopConfig
    with pytest.raises(AttributeError):
        core.definitely_not_a_symbol


def _scatter_cleared(det_or_fc, nodes, slots):
    """The eager scatter the masked clears replaced, as the reference."""
    idx = (np.asarray(nodes), np.asarray(slots))
    if isinstance(det_or_fc, StreamingDetector):
        return [det_or_fc.slot_hist.at[idx].set(0.0),
                det_or_fc.slot_prev.at[idx].set(0.0),
                det_or_fc.slot_score.at[idx].set(0.0)]
    return [det_or_fc.A.at[idx].set(0.0), det_or_fc.b.at[idx].set(0.0),
            det_or_fc.err.at[idx].set(1.0), det_or_fc.count.at[idx].set(0)]


def _track(det_or_fc):
    if isinstance(det_or_fc, StreamingDetector):
        return [det_or_fc.slot_hist, det_or_fc.slot_prev,
                det_or_fc.slot_score]
    return [det_or_fc.A, det_or_fc.b, det_or_fc.err, det_or_fc.count]


@pytest.mark.parametrize("kind", ["detector", "forecaster"])
def test_clear_slots_is_the_scatter_in_one_program(kind):
    """clear_slots is one masked update of fixed (N, S) shape: bitwise the
    scatter it replaced, for every number of cleared slots, and compiled
    once for all of them (a scatter compiles once per index count)."""
    from repro.control import detector as det_mod
    from repro.control import forecast as fc_mod

    rng = np.random.default_rng(7)
    n, s = 5, S_ON + S_OFF
    if kind == "detector":
        obj, fn = StreamingDetector(n), det_mod._clear_slot_track
        for _ in range(3):
            obj.update(rng.poisson(3.0, (n, s, 200)).astype(np.float32))
    else:
        obj, fn = fc_mod.QPSForecaster(n, S_ON), fc_mod._clear_fits
        s = S_ON
        for t in range(4):
            obj.update(40.0 * t, rng.uniform(100, 400, (n, s)),
                       rng.random((n, s)) < 0.8)
    sizes = []
    for count in (1, 3, 7, 2 * n):
        flat = rng.choice(n * s, count, replace=False)
        nodes, slots = np.divmod(flat, s)
        want = _scatter_cleared(obj, nodes, slots)
        obj.clear_slots(nodes, slots)
        for got, w in zip(_track(obj), want):
            assert got.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(got), np.asarray(w))
        sizes.append(fn._cache_size())
    assert sizes[1:] == sizes[:1] * 3, sizes


def test_decision_log_and_outcome_records_change_nothing():
    """The scheduler's decision log and the loop's outcome records only
    observe: a run with both on is bitwise the run with both off, and they
    hold one entry per offer and one record per loop window."""
    from repro.control import ForecastService
    from repro.core.scheduler import ICOFScheduler

    pods, gaps = bursty_trace(num_online=6, num_bursts=3, jobs_per_burst=3,
                              seed=4)
    runs = []
    for on in (False, True):
        q = _cheap_quantifier()
        cfg = scheduler_loop_config("ICO-F", proactive=True)
        svc = ForecastService(cfg.forecast, cfg.horizon)
        loop = ControlLoop(q, cfg, forecast_service=svc)
        sched = ICOFScheduler(q)
        if on:
            sched.decisions, loop.outcomes = [], []
        plan: dict = {}
        res = run_experiment(sched, pods, gaps, num_nodes=6, seed=9,
                             control_loop=loop, forecast=svc,
                             control_window=40, plan_out=plan)
        runs.append((res, plan["log"], sched, loop))
    (off, log_off, *_), (on, log_on, sched, loop) = runs
    assert on == off and log_on == log_off
    assert len(sched.decisions) == on.offers > 0
    # a refusal is an offer that placed nothing (a full slot is another)
    assert [d["chosen"] >= 0 for d in sched.decisions].count(False) <= \
        on.offers_rejected
    assert len(loop.outcomes) == loop.stats.steps
    assert sum(len(o["hot"]) for o in loop.outcomes) == \
        loop.stats.hotspots_flagged
    assert {"admit.quantify", "admit.score", "admit.place", "rollout",
            "detect"} <= set(on.phases)
