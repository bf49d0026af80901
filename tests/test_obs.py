"""Observability layer: trace recorder round-trip, action lifecycle
chains, admission-breakdown fidelity, the explain CLI, trust-gate events,
the zero-overhead (recorder-off bit-identical) invariant, the metrics
registry behind ControlStats, the bounded history ring buffer, and the
phase timers' spans.

The expensive fixture is ONE seeded 2-day ICO-F + proactive run traced
end-to-end and serialized/reloaded; every trace-shaped assertion reads
from that single run.
"""
import contextlib
import time
from collections import Counter as TallyCounter

import numpy as np
import pytest

from repro.cluster.experiment import bursty_trace, run_experiment
from repro.cluster.simulator import Cluster
from repro.cluster.workloads import OFFLINE_PROFILES, Pod
from repro.control import (
    ControlLoop,
    ControlLoopConfig,
    ForecastService,
    PolicyConfig,
    scheduler_loop_config,
)
from repro.core import ICOFScheduler, ICOScheduler, InterferenceQuantifier
from repro.obs import (
    AdmissionDecision,
    Counter,
    MetricsRegistry,
    NULL_RECORDER,
    PhaseTimers,
    Trace,
    TraceRecorder,
    WindowedHistogram,
    event_from_dict,
    load_trace,
)
from repro.obs import explain


def _cheap_quantifier():
    # constant predicted pod runqlat: admission stays meaningful (the
    # utilization terms differentiate nodes) and the RF cost disappears
    return InterferenceQuantifier(
        lambda X: np.full(np.asarray(X).shape[0], 0.1))


# ---------------- the one expensive traced run ----------------

@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """Seeded 2-day ICO-F + proactive run, traced, saved, and reloaded.

    Two diurnal periods are mandatory: the forecaster's leverage gate only
    opens after ~0.9 of a period, and the trust-gate-transition assertion
    needs the gate to actually flip during the run.
    """
    q = _cheap_quantifier()
    cfg = scheduler_loop_config("ICO-F", proactive=True)
    svc = ForecastService(cfg.forecast, cfg.horizon)
    loop = ControlLoop(q, cfg, forecast_service=svc)
    sched = ICOFScheduler(q)
    pods, gaps = bursty_trace(num_online=10, seed=3, burst_gap=(40, 70),
                              days=2.0)
    rec = TraceRecorder()
    result = run_experiment(sched, pods, gaps, num_nodes=6, seed=3,
                            control_loop=loop, forecast=svc,
                            control_window=40, recorder=rec)
    path = str(tmp_path_factory.mktemp("obs") / "trace.jsonl")
    saved = rec.save(path)
    return {
        "result": result,
        "loop": loop,
        "recorder": rec,
        "trace": load_trace(path),
        "path": path,
        "saved": saved,
    }


def test_trace_round_trip_counts(traced_run):
    rec, trace = traced_run["recorder"], traced_run["trace"]
    assert traced_run["saved"] == len(rec.events) == len(trace.events) > 0
    live = TallyCounter(type(ev).event for ev in rec.events)
    loaded = TallyCounter(type(ev).event for ev in trace.events)
    assert live == loaded
    # a 2-day proactive run exercises the whole taxonomy
    for kind in ("admission", "hotspot", "action_planned",
                 "action_executed", "action_verified", "trust_gate",
                 "phase_timings"):
        assert loaded[kind] > 0, f"no {kind} events in the 2-day trace"
    seqs = [ev.seq for ev in trace.events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    windows = [ev.window for ev in trace.events]
    assert windows == sorted(windows)  # emitted in window order


def test_every_executed_action_resolves(traced_run):
    """Planned -> Executed -> Verified/Discarded, reconstructed from the
    trace alone (the acceptance bar the bench chain check enforces)."""
    trace = traced_run["trace"]
    executed = trace.query("action_executed")
    assert executed, "the bursty 2-day run must apply some mitigation"
    last_w = trace.last_window()
    for ev in executed:
        chain = trace.action_chain(ev.action_id)
        planned = chain["planned"]
        assert planned is not None, f"action {ev.action_id} never planned"
        assert planned.node == ev.node and planned.action == ev.action
        assert planned.window == ev.window  # plan and apply in one step
        if ev.proactive or ev.window >= last_w:
            continue  # proactive actions are exempt; final window has no
                      # post-action window left to verify in
        verified = chain["verified"]
        assert verified is not None, (
            f"non-proactive action {ev.action_id} never resolved")
        assert verified.outcome in ("verified", "discarded")
        assert verified.window > ev.window


def test_stats_agree_with_trace(traced_run):
    """The metrics counters and the event stream tell the same story."""
    trace = traced_run["trace"]
    result = traced_run["result"]
    assert result.mitigations == len(trace.query("action_executed"))
    assert result.proactive_mitigations == len(
        trace.query("action_executed", proactive=True))
    placed = trace.query("admission", placed=True)
    assert result.placed == len(placed)
    assert result.queued_retries == len(
        trace.query("retry_drained", outcome="placed"))


def test_admission_breakdown_reproduces_score(traced_run):
    """The stored per-node terms decompose the stored score exactly:
    (1-ucpu)(1-umem) - intf_h - intf_p - forecast_term == score."""
    trace = traced_run["trace"]
    admissions = [ev for ev in trace.query("admission")
                  if "score" in ev.breakdown]
    assert admissions
    gated = 0
    for ev in admissions:
        bd = ev.breakdown
        ucpu = np.asarray(bd["utiliz_cpu"])
        umem = np.asarray(bd["utiliz_mem"])
        recomputed = ((1.0 - ucpu) * (1.0 - umem)
                      - np.asarray(bd["intf_h"]) - np.asarray(bd["intf_p"]))
        if "forecast_term" in bd:
            gated += 1
            recomputed = recomputed - np.asarray(bd["forecast_term"])
        score = np.asarray(bd["score"], np.float64)
        feasible = np.asarray(bd["feasible"], bool)
        assert np.allclose(recomputed[feasible], score[feasible], atol=1e-3)
        assert not np.isfinite(score[~feasible]).any()
        if ev.chosen >= 0:
            # 6dp serialization can collapse near-ties, so assert "chosen
            # scored maximally" rather than exact argmax identity
            assert score[ev.chosen] >= score.max() - 1e-5
    # the trust gate opened mid-run, so late admissions carry the ICO-F term
    assert gated > 0, "no admission recorded an open-gate forecast term"


def test_trust_gate_transition_recorded(traced_run):
    gates = traced_run["trace"].query("trust_gate")
    opened = [ev for ev in gates if ev.opened]
    assert opened, "2-day run must record at least one gate opening"
    for ev in opened:
        assert ev.trusted_slots > 0
        # leverage/rel-err evidence rides along when any slot has samples
        assert ev.leverage == ev.leverage  # not NaN on an opening flip


def test_hotspot_events_attributed(traced_run):
    hotspots = traced_run["trace"].query("hotspot")
    assert hotspots
    channels = {ev.channel for ev in hotspots}
    assert channels <= {"drift", "acute", "forecast"}
    assert "forecast" in channels, "proactive run must flag predicted drift"


def test_phase_timings_recorded(traced_run):
    tms = traced_run["trace"].query("phase_timings")
    assert tms
    phases = set()
    for ev in tms:
        phases |= set(ev.timings)
        for seconds in ev.timings.values():
            assert seconds >= 0.0  # per-window wall-clock seconds per phase
    assert {"rollout", "detect", "forecast"} <= phases


def test_explain_from_loaded_trace(traced_run, capsys):
    trace, path = traced_run["trace"], traced_run["path"]
    summary = explain.summarize(trace)
    assert "admissions" in summary and "actions" in summary
    uid = trace.query("admission", placed=True)[0].uid
    text = explain.explain_pod(trace, uid)
    assert f"uid={uid}" in text and "utiliz_cpu" in text and "score" in text
    aid = trace.query("action_executed")[0].action_id
    text = explain.explain_action(trace, aid)
    assert "planned:" in text and "executed:" in text
    # the CLI drives the same paths straight off the JSONL file
    assert explain.main([path, "--summary"]) == 0
    assert explain.main([path, "--pod", str(uid)]) == 0
    assert explain.main([path, "--action", str(aid)]) == 0
    assert explain.main([path, "--trust"]) == 0
    capsys.readouterr()


# ---------------- zero-overhead invariant ----------------

def _short_run(recorder):
    q = _cheap_quantifier()
    pods, gaps = bursty_trace(num_online=8, num_bursts=2, jobs_per_burst=3,
                              seed=5, burst_gap=(20, 30),
                              job_duration=(60, 100))
    loop = ControlLoop(q, ControlLoopConfig())
    return run_experiment(ICOScheduler(q), pods, gaps, num_nodes=5, seed=5,
                          control_loop=loop, control_window=20,
                          recorder=recorder)


def test_recorder_off_bit_identical():
    """Tracing only observes: identical results with recorder on/off/null."""
    r_off = _short_run(None)
    rec = TraceRecorder()
    r_on = _short_run(rec)
    r_null = _short_run(NULL_RECORDER)
    assert r_on == r_off  # dataclass equality: every float bit-identical
    assert r_null == r_off
    assert len(rec.events) > 0 and len(NULL_RECORDER) == 0


def test_traced_smoke_experiment_is_fast():
    """A ~200-tick traced experiment stays interactive (CI fast-lane bar)."""
    q = _cheap_quantifier()
    pods, gaps = bursty_trace(num_online=6, num_bursts=2, jobs_per_burst=2,
                              seed=1, burst_gap=(20, 30),
                              job_duration=(50, 80))
    rec = TraceRecorder()
    t0 = time.time()
    result = run_experiment(ICOScheduler(q), pods, gaps, num_nodes=4, seed=1,
                            control_loop=ControlLoop(q, ControlLoopConfig()),
                            control_window=20, settle_ticks=20, recorder=rec)
    elapsed = time.time() - t0
    assert elapsed < 30.0, f"traced smoke run took {elapsed:.1f}s"
    assert result.placed > 0
    admissions = rec.query("admission")
    assert admissions and all(ev.placed is not None for ev in admissions)
    assert rec.query("phase_timings")


# ---------------- events / recorder units ----------------

def test_event_dict_round_trip():
    ev = AdmissionDecision(scheduler="ICO", workload="web_search", qps=220.0,
                           online=True, cpu_demand=5.0, mem_demand=4.0,
                           chosen=2, uid=7, placed=True,
                           breakdown={"score": np.array([0.1, -np.inf, 0.3]),
                                      "feasible": np.array([True, False, True])})
    ev.seq, ev.window, ev.t = 3, 1, 40.0
    back = event_from_dict(ev.to_dict())
    assert isinstance(back, AdmissionDecision)
    assert back.chosen == 2 and back.uid == 7 and back.placed is True
    assert back.breakdown["score"] == [0.1, -np.inf, 0.3]
    assert back.seq == 3 and back.window == 1 and back.t == 40.0
    # unknown event types degrade to GenericEvent instead of failing
    odd = event_from_dict({"event": "from_the_future", "seq": 9, "zap": 1})
    assert type(odd).event == "generic" and odd.seq == 9


def test_resolve_admission_binds_latest_unresolved():
    rec = TraceRecorder()
    rec.begin_window(0.0)
    rec.emit(AdmissionDecision(scheduler="ICO", chosen=1))
    rec.resolve_admission(uid=11, placed=True)
    rec.emit(AdmissionDecision(scheduler="ICO", chosen=-1))
    rec.resolve_admission(uid=-1, placed=False, retry=True)
    first, second = rec.query("admission")
    assert (first.uid, first.placed, first.retry) == (11, True, False)
    assert (second.uid, second.placed, second.retry) == (-1, False, True)
    rec.resolve_admission(uid=99, placed=True)  # nothing unresolved: no-op
    assert rec.query("admission", uid=99) == []


# ---------------- metrics registry / ControlStats view ----------------

def test_metrics_registry():
    m = MetricsRegistry()
    assert m.inc("a.x") == 1.0 and m.inc("a.x", 2.5) == 3.5
    m.inc("a.y")
    m.inc("b.z")
    assert m.counters("a.") == {"a.x": 3.5, "a.y": 1.0}
    m.set("g", 7.0)
    assert m.value("g") == 7.0 and m.value("never_touched") == 0.0
    for v in (1.0, 2.0, 3.0, 4.0):
        m.observe("lat", v)
    h = m.histogram("lat")
    assert h.mean() == 2.5 and h.count == 4
    snap = m.snapshot()
    assert snap["counters"]["b.z"] == 1.0
    assert snap["histograms"]["lat"]["count"] == 4


def test_windowed_histogram_ring_is_bounded():
    h = WindowedHistogram(maxlen=8)
    for v in range(100):
        h.observe(float(v))
    assert len(h.ring) == 8          # only the recent window is resident
    assert h.count == 100            # lifetime stats stay exact
    assert h.mean() == sum(range(100)) / 100
    assert h.percentile(50) == 95.5  # over the ring: values 92..99


def test_control_stats_is_computed_view():
    loop = ControlLoop(_cheap_quantifier())
    m = loop.metrics
    m.inc("actions_applied")
    m.inc("applied_kind.migrate_online")
    m.inc("hotspots_flagged", 3)
    s = loop.stats
    assert s.actions_applied == 1 and s.hotspots_flagged == 3
    assert s.by_kind == {"migrate_online": 1}
    assert s.mean_calibration_abs_error == 0.0  # nothing verified yet
    m.inc("actions_verified", 2)
    m.inc("calibration_abs_error", 30.0)
    m.inc("predicted_reduction", 120.0)
    s = loop.stats
    assert s.mean_calibration_abs_error == pytest.approx(15.0)
    assert s.calibration_error() == pytest.approx(30.0 / 120.0)
    # the view is a snapshot: mutating it does not touch the registry
    s.actions_applied = 99
    assert loop.stats.actions_applied == 1


# ---------------- history ring buffer ----------------

def test_history_ring_buffer_bounded():
    cfg = ControlLoopConfig(history_limit=3, policy=PolicyConfig(budget=0.0))
    loop = ControlLoop(_cheap_quantifier(), cfg)
    assert loop.history.maxlen == 3
    cluster = Cluster(num_nodes=3, seed=0)
    cluster.rollout(20)
    prof = OFFLINE_PROFILES["graph_analytics"]
    for _ in range(3):  # overload node 0 so every window flags hot
        job = Pod("graph_analytics", 0.0, False, duration=800)
        job.cpu_demand = 12.0
        job.mem_demand = 12.0 * prof.mem_per_core
        assert cluster.place(job, 0)
    entries_seen = 0
    for _ in range(10):
        cluster.rollout(10)
        loop.step(cluster)
        entries_seen = max(entries_seen, len(loop.history))
    assert entries_seen == 3, "hot windows must have overflowed the ring"
    assert len(loop.history) == 3
    steps = [h["step"] for h in loop.history]
    assert steps == sorted(steps) and steps[-1] > 3  # oldest entries evicted
    for h in loop.history:
        assert {"step", "window", "t", "hot_nodes"} <= set(h)
        assert h["window"] == h["step"] - 1  # no recorder: step-derived


def test_in_memory_trace_matches_loaded_explain(traced_run):
    """Trace(rec.events) (numpy payloads) and load_trace (list payloads)
    explain a pod identically, modulo float formatting."""
    rec, trace = traced_run["recorder"], traced_run["trace"]
    uid = trace.query("admission", placed=True)[0].uid
    live = explain.explain_pod(Trace(rec.events), uid)
    loaded = explain.explain_pod(trace, uid)
    assert live.splitlines()[0] == loaded.splitlines()[0]
    assert len(live.splitlines()) == len(loaded.splitlines())


# ---------------- phase timers as spans ----------------

def _recording_annotation(log):
    """A span factory that logs each span's entry and exit."""
    @contextlib.contextmanager
    def annotate(name, **kwargs):
        log.append(("enter", name))
        try:
            yield
        finally:
            log.append(("exit", name))
    return annotate


def test_phase_timers_open_prefixed_nested_spans():
    log = []
    timers = PhaseTimers("repro.test", _recording_annotation(log))
    with timers.phase("outer"):
        with timers.phase("inner"):
            pass
    with pytest.raises(RuntimeError):
        with timers.phase("inner"):
            raise RuntimeError("a failing phase still closes its span")
    assert log == [("enter", "repro.test.outer"),
                   ("enter", "repro.test.inner"),
                   ("exit", "repro.test.inner"),
                   ("exit", "repro.test.outer"),
                   ("enter", "repro.test.inner"),
                   ("exit", "repro.test.inner")]
    # the accumulators keep the bare phase names
    assert timers.counts == {"outer": 1, "inner": 2}
    assert set(timers.pop_window()) == {"outer", "inner"}
    assert set(timers.summary()) == {"outer", "inner"}
    assert timers.totals["outer"] >= 0.0 and timers.pop_window() == {}


def test_phase_timers_without_factory_open_no_span(monkeypatch):
    import jax

    log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        _recording_annotation(log))
    timers = PhaseTimers()
    with timers.phase("rollout"):
        pass
    assert log == [] and timers.counts == {"rollout": 1}
