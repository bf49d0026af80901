"""Batched rollout core: ClusterState pytree, scan/vmap paths, event replay.

The load-bearing bar here is **parity**: the scanned core (`rollout_scan`,
`scan_windows`, `batched_rollout`) must reproduce the legacy per-chunk
Python loop — same key stream, same telemetry, same placements — so the
fast paths in `run_experiment` / `replay_plan_batched` measure the same
simulation the shell-driven runs do.
"""
import contextlib
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.cluster import state as cstate
from repro.cluster import workloads as W
from repro.cluster.simulator import CHUNK, Cluster, NodeSpec, S_ON
from repro.cluster.workloads import Pod


def _online(qps=300.0, name="web_search"):
    prof = W.ONLINE_PROFILES[name]
    p = Pod(name, qps, True)
    p.cpu_demand = prof.cpu_per_qps * qps + prof.cpu_base
    p.mem_demand = prof.mem_per_qps * qps + prof.mem_base
    return p


def _offline(cores=4.0, duration=200, name="in_memory_analytics"):
    p = Pod(name, 0.0, False)
    p.cpu_demand, p.mem_demand = cores, 8.0
    p.duration = duration
    return p


def test_nodespec_frozen():
    spec = NodeSpec()
    with pytest.raises(dataclasses.FrozenInstanceError):
        spec.cores = 64.0
    # two clusters can no longer share (and corrupt) one default instance
    a, b = Cluster(num_nodes=1), Cluster(num_nodes=1)
    assert a.spec == b.spec and a.spec is not b.spec or a.spec is b.spec


def test_state_dict_compat():
    c = Cluster(num_nodes=3, seed=0)
    assert np.asarray(c.state["on_active"]).shape == (3, S_ON)
    assert len(dict(c.state.items())) == 12
    assert set(c.state.keys()) == {f.name for f in
                                   dataclasses.fields(cstate.ClusterState)}


def test_pure_transforms_roundtrip():
    st = cstate.ClusterState.create(2)
    st = cstate.place_online(st, 0, 0, 0, 200.0, 0.3)
    assert bool(st.on_active[0, 0])
    st = cstate.migrate_online(st, 0, 0, 1, 2)
    assert not bool(st.on_active[0, 0]) and bool(st.on_active[1, 2])
    assert float(st.on_qps_mean[1, 2]) == 200.0
    st = cstate.resize_online(st, 1, 2, 150.0)
    assert float(st.on_qps_mean[1, 2]) == 150.0
    st = cstate.evict_online(st, 1, 2)
    assert not bool(np.asarray(st.on_active).any())

    st = cstate.place_offline(st, 1, 3, 4.0, 6.4, 10.0, 1.2, 50)
    st = cstate.resize_offline(st, 1, 3, 2.0, 3.2, 5.0, 100)
    assert float(st.off_cores[1, 3]) == 2.0
    assert int(st.off_remaining[1, 3]) == 100
    st = cstate.migrate_offline(st, 1, 3, 0, 0)
    assert bool(st.off_active[0, 0]) and not bool(st.off_active[1, 3])
    # kernel-side expiry leaves parameters behind; reconcile clears them
    st = st.replace(off_active=jnp.zeros_like(st.off_active))
    st, stale = cstate.reconcile(st)
    assert bool(np.asarray(stale)[0, 0])
    assert float(st.off_cores[0, 0]) == 0.0


def _seeded_cluster(seed=5):
    c = Cluster(num_nodes=4, seed=seed)
    c.place(_online(300.0), 0)
    c.place(_online(220.0, "web_serving"), 1)
    c.place(_offline(4.0, duration=200), 2)
    return c


def test_rollout_scan_matches_rollout():
    """Bitwise parity: same key stream, same telemetry, same final state."""
    a, b = _seeded_cluster(), _seeded_cluster()
    sa = a.rollout(40)
    sb = b.rollout_scan(40)
    for k in sa:
        np.testing.assert_array_equal(np.asarray(sa[k]), np.asarray(sb[k]),
                                      err_msg=k)
    # mutate identically between windows, then roll again
    for c in (a, b):
        c.migrate(0, 3)
        c.resize(2, cores=2.0)
    sa, sb = a.rollout(40), b.rollout_scan(40)
    for k in sa:
        np.testing.assert_array_equal(np.asarray(sa[k]), np.asarray(sb[k]),
                                      err_msg=k)
    for f in dataclasses.fields(cstate.ClusterState):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.state, f.name)),
            np.asarray(getattr(b.state, f.name)), err_msg=f.name)
    np.testing.assert_array_equal(np.asarray(a.key), np.asarray(b.key))


def test_event_replay_matches_shell():
    """The padded event plan (place/migrate/evict/resize + expiry-driven
    reconcile) replayed through `batched_rollout` reproduces the
    shell-driven run's RT stream and final occupancy."""
    seed = 9
    c = Cluster(num_nodes=4, seed=seed)
    rts = []
    c.place(_online(320.0), 0)                    # uid 0
    c.place(_offline(4.0, duration=70), 1)        # uid 1: expires mid-run
    rts.append(c.rollout(40)["rt"])
    c.place(_online(250.0, "web_serving"), 2)     # uid 2
    c.migrate(0, 3)
    c.resize(1, cores=2.0)                        # stretches remaining
    rts.append(c.rollout(40)["rt"])
    c.resize(2, qps=180.0)
    c.remove(0)                                   # explicit evict
    rts.append(c.rollout(40)["rt"])
    rts.append(c.rollout(40)["rt"])
    ref_rt = np.concatenate([np.asarray(r) for r in rts])  # (160, N, S_ON)

    cpw = 4
    num_windows = int(c.t) // CHUNK // cpw
    events = cstate.extract_plan(c.log, 0.0, num_windows, cpw)
    _, ks = cstate.chunk_key_stream(jax.random.PRNGKey(seed),
                                    num_windows * cpw)
    keys = ks.reshape(num_windows, cpw, -1)[None]          # B=1
    state0 = cstate.ClusterState.create(4)
    profiles = {k: jnp.asarray(v) for k, v in W.online_arrays().items()}
    final, outs = cstate.batched_rollout(state0, profiles, 0.0, keys, events)

    rep_rt = np.asarray(outs["rt"])[0].reshape(ref_rt.shape)
    np.testing.assert_allclose(rep_rt, ref_rt, atol=1e-3)
    np.testing.assert_array_equal(np.asarray(final["state"].on_active)[0],
                                  np.asarray(c.state.on_active))
    np.testing.assert_array_equal(np.asarray(final["state"].off_active)[0],
                                  np.asarray(c.state.off_active))


def _tiny_experiment(fast, plan_out=None):
    from repro.cluster.experiment import _arrival_trace, run_experiment
    from repro.core import ICOScheduler, InterferenceQuantifier

    sched = ICOScheduler(InterferenceQuantifier(
        lambda x: np.asarray(x)[:, 0] * 0.1))
    pods, gaps = _arrival_trace(12, seed=3)
    return run_experiment(sched, pods, gaps, num_nodes=6, seed=5,
                          fast=fast, plan_out=plan_out)


def test_run_experiment_fast_path_matches_legacy():
    r_fast, r_slow = _tiny_experiment(True), _tiny_experiment(False)
    assert (r_fast.placed, r_fast.rejected) == (r_slow.placed, r_slow.rejected)
    for f in ("avg_rt", "p90_rt", "p99_rt", "cpu_util_std", "mem_util_std"):
        assert np.isclose(getattr(r_fast, f), getattr(r_slow, f),
                          rtol=1e-6), f


def test_replay_plan_batched_reference_parity():
    from repro.cluster.experiment import replay_plan_batched

    plan = {}
    ref = _tiny_experiment(True, plan_out=plan)
    batch = replay_plan_batched(plan, sim_seeds=[5, 6])
    assert batch["num_windows"] > 0 and len(batch["seeds"]) == 2
    by_seed = {e["sim_seed"]: e for e in batch["seeds"]}
    # the entry replayed under the reference run's sim seed IS that run
    assert np.isclose(by_seed[5]["p99_rt"], ref.p99_rt, rtol=1e-3)
    assert np.isclose(by_seed[5]["avg_rt"], ref.avg_rt, rtol=1e-3)
    # a different seed is a genuinely different telemetry stream
    assert by_seed[6]["avg_rt"] != by_seed[5]["avg_rt"]


def test_replay_ignores_actions_at_run_end():
    """The control step after a run's last window may act at t_end.  When
    t_end falls on a window boundary that entry lies past the replayed
    span; it changes no simulated tick, so the replay is the one without
    it."""
    from repro.cluster.experiment import replay_plan_batched

    plan = {"log": [("place_on", 0.0, 0, 0, 0, 300.0, 0.4),
                    ("place_on", 0.0, 1, 0, 1, 250.0, 0.1)],
            "t_end": 400.0, "num_nodes": 3}
    late = dict(plan, log=plan["log"] + [("migrate_on", 400.0, 0, 0, 2, 0)])
    ref = replay_plan_batched(plan, sim_seeds=[1])
    got = replay_plan_batched(late, sim_seeds=[1])
    assert got["seeds"] == ref["seeds"]
    assert got["num_windows"] == ref["num_windows"] == 10


def test_replay_phases_are_nested_spans(monkeypatch):
    """Each replay phase is a ``repro.replay.*`` span, nested as the call
    runs, and ``phases`` gives the same split on the host's clock."""
    from repro.cluster.experiment import replay_plan_batched

    log = []

    @contextlib.contextmanager
    def annotate(name, **kwargs):
        log.append(("enter", name))
        try:
            yield
        finally:
            log.append(("exit", name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotate)
    plan = {"log": [("place_on", 0.0, 0, 0, 0, 300.0, 0.4)],
            "t_end": 400.0, "num_nodes": 3}
    out = replay_plan_batched(plan, sim_seeds=[1, 2])
    steps = ["+call", "+inputs", "+plan", "-plan", "+keys", "-keys",
             "-inputs", "+engine", "-engine", "+reduce", "-reduce", "-call"]
    assert log == [("enter" if s[0] == "+" else "exit",
                    f"repro.replay.{s[1:]}") for s in steps]
    ph = out["phases"]
    assert set(ph) == {"call", "inputs", "plan", "keys", "engine", "reduce"}
    assert out["wall_s"] == ph["engine"]
    assert ph["call"] >= ph["inputs"] + ph["engine"] + ph["reduce"]
    assert ph["inputs"] >= ph["plan"] + ph["keys"]


def test_batched_rollout_seed_axis_varies():
    """Two seeds in one vmapped call: same plan, different telemetry."""
    state0 = cstate.ClusterState.create(3)
    profiles = {k: jnp.asarray(v) for k, v in W.online_arrays().items()}
    events = cstate.extract_plan(
        [("place_on", 0.0, 0, 0, 0, 300.0, 0.4)], 0.0, 2, 2)
    keys = jnp.stack([
        cstate.chunk_key_stream(jax.random.PRNGKey(s), 4)[1].reshape(2, 2, -1)
        for s in (0, 1)])
    final, outs = cstate.batched_rollout(state0, profiles, 0.0, keys, events)
    rt = np.asarray(outs["rt"])
    assert rt.shape[0] == 2
    active = rt[:, :, :, 0, 0]
    assert not np.allclose(active[0], active[1])
    # the plan (occupancy) is identical across the seed axis
    np.testing.assert_array_equal(np.asarray(final["state"].on_active)[0],
                                  np.asarray(final["state"].on_active)[1])


def _eager_chunk_key_stream(key, num_chunks):
    """The split loop ``chunk_key_stream`` replaced: one eager dispatch a
    chunk, then a stack.  The reference for its bits."""
    ks = []
    for _ in range(num_chunks):
        key, k = jax.random.split(key)
        ks.append(k)
    return key, jnp.stack(ks)


def _eager_replay_keys(sim_seeds, num_windows, cpw):
    return jnp.stack([
        _eager_chunk_key_stream(jax.random.PRNGKey(int(s)),
                                num_windows * cpw)[1]
        .reshape(num_windows, cpw, -1)
        for s in sim_seeds])


KEY_SEEDS = (0, 7, 2**31 - 1, 3_000_000_017)


@pytest.mark.parametrize("count", [1, 4, 1024])
@pytest.mark.parametrize("seed", KEY_SEEDS)
def test_chunk_key_stream_matches_eager_loop(seed, count):
    key = jax.random.PRNGKey(seed)
    want_key, want = _eager_chunk_key_stream(key, count)
    got_key, got = cstate.chunk_key_stream(key, count)
    assert got.shape == (count, 2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_key), np.asarray(want_key))


@pytest.mark.parametrize("num_windows,cpw", [(1, 1), (2, 2), (256, 4)])
def test_replay_key_stream_matches_eager_loop(num_windows, cpw):
    """Every seed's stream in one program, seeds of 2**31 and above
    included, is bitwise the per-seed eager loop."""
    sk = cstate.seed_keys(KEY_SEEDS)
    np.testing.assert_array_equal(
        np.asarray(sk),
        np.stack([np.asarray(jax.random.PRNGKey(s)) for s in KEY_SEEDS]))
    got = cstate.replay_key_stream(sk, num_windows, cpw)
    assert got.shape == (len(KEY_SEEDS), num_windows, cpw, 2)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(_eager_replay_keys(KEY_SEEDS, num_windows, cpw)))


_KEY_PLAN = {"log": [("place_on", 0.0, 0, 0, 0, 300.0, 0.4),
                     ("place_on", 0.0, 1, 0, 1, 250.0, 0.1),
                     ("migrate_on", 400.0, 0, 0, 2, 0)],
             "t_end": 800.0, "num_nodes": 3}


def test_replay_inputs_keys_match_eager_stack():
    from repro.cluster.experiment import replay_inputs

    seeds = tuple(range(100, 120))
    inp = replay_inputs(_KEY_PLAN, seeds)
    cpw = inp["span"] // CHUNK
    want = _eager_replay_keys(seeds, inp["padded_windows"], cpw)
    np.testing.assert_array_equal(np.asarray(inp["keys"]), np.asarray(want))


def test_replay_compiled_keys_match_eager_replay(monkeypatch):
    """The replay's answers are bitwise those of the eager key stream, and
    building the keys is now a small part of a call."""
    from repro.cluster.experiment import replay_plan_batched

    seeds = tuple(range(20))
    replay_plan_batched(_KEY_PLAN, sim_seeds=seeds)        # compiles
    new = replay_plan_batched(_KEY_PLAN, sim_seeds=seeds)
    monkeypatch.setattr(
        cstate, "replay_key_stream",
        lambda sk, num_windows, cpw: _eager_replay_keys(seeds, num_windows,
                                                        cpw))
    old = replay_plan_batched(_KEY_PLAN, sim_seeds=seeds)
    for g, w in zip(new["seeds"], old["seeds"], strict=True):
        for k in ("sim_seed", "avg_rt", "p90_rt", "p99_rt", "hot_windows"):
            assert g[k] == w[k], k
    ph = new["phases"]
    assert ph["keys"] < 0.05 * ph["call"]
    assert ph["keys"] < 0.1 * old["phases"]["keys"]
