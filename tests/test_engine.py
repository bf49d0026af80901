"""Device-parallel, compile-once rollout engine.

Three properties carry this layer:

* **Shard parity** — ``batched_rollout(devices=N)`` (shard_map over a 1-D
  "seeds" mesh) is bitwise the single-device vmap, including the padding
  path when the batch does not divide the device count.
* **Donation safety** — the donated carries (state at ``rollout_chunks``,
  the stacked batched state) really are consumed, and consuming them does not perturb results (the golden
  digests in test_fleet.py stay bitwise on the same entry points).
* **Compile-once bucketing** — two different plans in the same
  power-of-two size class replay through ONE compiled executable, and the
  bucketing padding is bitwise invisible on the real window prefix.
"""
from __future__ import annotations

import contextlib
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.cluster import state as cstate
from repro.cluster import workloads as W

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _profiles():
    return {k: jnp.asarray(v) for k, v in W.online_arrays().items()}


def _scenario(num_nodes=3, num_windows=2, cpw=2, seeds=(0, 1, 2), log=None):
    log = log or [("place_on", 0.0, 0, 0, 0, 300.0, 0.4),
                  ("place_off", 10.0, 1, 0, 2.0, 4.0, 8.0, 1.6, 25)]
    events = cstate.extract_plan(log, 0.0, num_windows, cpw)
    keys = jnp.stack([
        cstate.chunk_key_stream(jax.random.PRNGKey(s), num_windows * cpw)[1]
        .reshape(num_windows, cpw, -1)
        for s in seeds
    ])
    return cstate.ClusterState.create(num_nodes), _profiles(), keys, events


def _trees_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# ------------------------------------------------------------ shard parity


def test_shard_request_clamps_to_available_devices():
    """devices=4 on a single-device runtime raises instead of clamping:
    a silent fallback to the vmap engine would pass a one-device run off
    as a four-device one.  devices=1 is the vmap engine, bitwise."""
    state0, profiles, keys, events = _scenario()
    assert jax.device_count() == 1
    with pytest.raises(ValueError, match="4-device seed mesh"):
        cstate.batched_rollout(state0, profiles, 0.0, keys, events,
                               devices=4)
    ref = cstate.batched_rollout(state0, profiles, 0.0, keys, events)
    got = cstate.batched_rollout(state0, profiles, 0.0, keys, events,
                                 devices=1)
    assert _trees_equal(ref, got)


def test_shard_map_parity_two_devices_subprocess():
    """With 2 forced host devices, the sharded engine — including the
    pad-to-device-multiple path (B=3 on 2 devices) — is bitwise the vmap
    engine.  Subprocess because XLA_FLAGS must be set before jax loads."""
    code = textwrap.dedent("""\
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.cluster import state as cstate
        from repro.cluster import workloads as W

        assert jax.device_count() == 2, jax.device_count()
        state0 = cstate.ClusterState.create(2)
        profiles = {k: jnp.asarray(v) for k, v in W.online_arrays().items()}
        events = cstate.extract_plan(
            [("place_on", 0.0, 0, 0, 0, 300.0, 0.4)], 0.0, 2, 2)
        keys = jnp.stack([
            cstate.chunk_key_stream(jax.random.PRNGKey(s), 4)[1]
            .reshape(2, 2, -1) for s in (0, 1, 2)])
        ref = cstate.batched_rollout(state0, profiles, 0.0, keys, events)
        got = cstate.batched_rollout(state0, profiles, 0.0, keys, events,
                                     devices=2)
        leaves = zip(jax.tree_util.tree_leaves(ref),
                     jax.tree_util.tree_leaves(got))
        assert all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in leaves)
        print("OK")
    """)
    env = dict(os.environ,
               XLA_FLAGS=os.environ.get("XLA_FLAGS", "")
               + " --xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


# -------------------------------------------------------------- donation


def test_donated_carries_are_consumed():
    """rollout_chunks donates the state.  On backends implementing donation
    the input must be dead afterwards — reuse would silently read freed
    buffers."""
    profiles = _profiles()
    fleet = cstate.FleetParams.uniform(3)
    st = cstate.ClusterState.create(3)
    _, ks = cstate.chunk_key_stream(jax.random.PRNGKey(0), 4)
    new_st, _ = cstate.rollout_chunks(st, profiles, fleet, 0.0, ks)
    assert st.cpu_sum.is_deleted()
    assert not new_st.cpu_sum.is_deleted()


def test_stacked_batched_state_is_donated():
    state0, profiles, keys, events = _scenario()
    stacked = jax.tree_util.tree_map(lambda x: jnp.stack([x] * 3), state0)
    ref = cstate.batched_rollout(state0, profiles, 0.0, keys, events)
    got = cstate.batched_rollout(stacked, profiles, 0.0, keys, events)
    assert stacked.cpu_sum.is_deleted()
    # a stacked copy of the shared state replays the shared results
    assert np.allclose(np.asarray(ref[1]["rt"]), np.asarray(got[1]["rt"]))


# ----------------------------------------------------- compile-once bucketing


def test_bucketed_plan_prefix_is_bitwise():
    """bucket=True pads windows (3 -> 4) and events-per-chunk (3 -> 4);
    the real-window prefix of the replay must be bitwise unchanged."""
    log = [("place_on", 0.0, 0, 0, 0, 300.0, 0.4),
           ("place_on", 0.0, 1, 0, 1, 250.0, 1.1),
           ("place_on", 0.0, 2, 0, 2, 200.0, 2.0),
           ("place_off", 20.0, 1, 0, 2.0, 4.0, 8.0, 1.6, 30)]
    state0, profiles, keys, events = _scenario(num_windows=3, log=log)
    ev_b = cstate.extract_plan(log, 0.0, 3, 2, bucket=True)
    assert ev_b["op"].shape == (4, 2, 4)
    assert events["op"].shape == (3, 2, 3)
    keys_b = jnp.stack([
        cstate.chunk_key_stream(jax.random.PRNGKey(s), 4 * 2)[1]
        .reshape(4, 2, -1) for s in (0, 1, 2)])
    # prefix-stable key stream: the first 3 windows' keys are unchanged
    np.testing.assert_array_equal(np.asarray(keys_b[:, :3]), np.asarray(keys))
    ref = cstate.batched_rollout(state0, profiles, 0.0, keys, events)
    got = cstate.batched_rollout(state0, profiles, 0.0, keys_b, ev_b)
    for k in ("rt", "qps", "cpu_util", "mem_util", "hot"):
        np.testing.assert_array_equal(
            np.asarray(got[1][k])[:, :3], np.asarray(ref[1][k]), err_msg=k)


def test_same_size_class_plans_share_one_executable():
    """Two different logs in the same power-of-two size class must hit the
    same compiled executable — the jit cache grows by exactly one entry
    for the pair."""
    log_a = [("place_on", 0.0, 0, 0, 0, 300.0, 0.4),
             ("place_on", 0.0, 1, 0, 1, 250.0, 1.0),
             ("place_on", 0.0, 2, 0, 2, 220.0, 2.0)]  # 3 events -> class 4
    log_b = [("place_off", 0.0, n, 0, 2.0, 4.0, 8.0, 1.5, 35)
             for n in range(4)]                       # 4 events -> class 4
    # 5-node scenario: a shape no other test compiles, so the cache delta
    # below is exactly this test's
    seeds = (0, 1)
    state0 = cstate.ClusterState.create(5)
    profiles = _profiles()
    fn = cstate._batched_fn(stacked=False)
    before = fn._cache_size()
    walls = []
    for log in (log_a, log_b):
        ev = cstate.extract_plan(log, 0.0, 3, 2, bucket=True)
        keys = jnp.stack([
            cstate.chunk_key_stream(
                jax.random.PRNGKey(s), ev["op"].shape[0] * 2)[1]
            .reshape(-1, 2, 2) for s in seeds])
        t0 = time.time()
        _, outs = cstate.batched_rollout(state0, profiles, 0.0, keys, ev)
        jax.block_until_ready(outs["rt"])
        walls.append(time.time() - t0)
    assert fn._cache_size() == before + 1, (
        "same-size-class plans must not recompile")
    # the second replay skipped tracing+compilation entirely
    assert walls[1] < walls[0]


def test_next_pow2():
    assert [cstate._next_pow2(n) for n in (0, 1, 2, 3, 5, 8, 9)] \
        == [1, 1, 2, 4, 8, 8, 16]


# ---------------------------------------------------------- phase timers


@pytest.mark.parametrize("controlled", [False, True])
def test_rollout_span_holds_the_device_wait(monkeypatch, controlled):
    """The structural form of ``test_rollout_phase_attribution``: every
    ``block_until_ready`` of a ``run_experiment`` runs while the
    ``repro.loop.rollout`` span is the innermost one open, with or without
    a control loop, so a profile charges the device wait to the rollout."""
    from repro.cluster.experiment import _arrival_trace, run_experiment
    from repro.control import ControlLoop
    from repro.core import ICOScheduler, InterferenceQuantifier

    open_spans, opened, waits = [], set(), []

    @contextlib.contextmanager
    def annotate(name, **kwargs):
        opened.add(name)
        open_spans.append(name)
        try:
            yield
        finally:
            open_spans.pop()

    block = jax.block_until_ready

    def spy(x):
        waits.append(tuple(open_spans))
        return block(x)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotate)
    monkeypatch.setattr(jax, "block_until_ready", spy)
    quant = InterferenceQuantifier(lambda x: np.asarray(x)[:, 0] * 0.1)
    loop = ControlLoop(quant) if controlled else None
    pods, gaps = _arrival_trace(6, seed=3)
    run_experiment(ICOScheduler(quant), pods, gaps, num_nodes=4, seed=5,
                   control_loop=loop, control_window=40)
    assert waits and all(w[-1:] == ("repro.loop.rollout",) for w in waits)
    want = {"repro.loop.rollout"}
    if controlled:
        want |= {f"repro.loop.{p}"
                 for p in ("snapshot", "verify", "forecast", "detect")}
    assert want <= opened, opened


def test_rollout_phase_attribution():
    """The rollout phase must absorb the device compute it dispatches
    (block_until_ready inside the timed region): the run's phase timers
    (``ExperimentResult.phases``: the loop's and the scheduler's
    ``admit.*``) cover most of the end-to-end wall, and rollout dominates
    the loop's.  Without the block, the compute drains under untimed host
    code and rollout's share collapses.

    An untimed run on the same inputs compiles every phase's programs
    first: which of them a worker has compiled before depends on the
    test files it ran, and a phase that compiles inside the timed run
    measures the compiler, not the phase."""
    from repro.cluster.experiment import _arrival_trace, run_experiment
    from repro.control import ControlLoop
    from repro.core import ICOScheduler, InterferenceQuantifier

    quant = InterferenceQuantifier(lambda x: np.asarray(x)[:, 0] * 0.1)
    sched = ICOScheduler(quant)
    pods, gaps = _arrival_trace(10, seed=3)
    run_experiment(sched, pods, gaps, num_nodes=6, seed=5,
                   control_loop=ControlLoop(quant), control_window=40)
    loop = ControlLoop(quant)
    t0 = time.time()
    res = run_experiment(sched, pods, gaps, num_nodes=6, seed=5,
                         control_loop=loop, control_window=40)
    wall = time.time() - t0
    totals = dict(loop.timers.totals)
    covered = sum(res.phases.values())
    assert totals.get("rollout", 0.0) > 0.0
    # generous slack: retry bookkeeping and numpy conversions are
    # legitimately untimed, but they are small next to the phases
    assert covered >= 0.5 * wall, (res.phases, wall)
    assert totals["rollout"] >= 0.5 * sum(totals.values()), (totals, wall)
