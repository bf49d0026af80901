"""The replay driver against the plain reference, at a tiny size on the
CPU: a sound run is correct, the control and each fault are not."""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench.tests import tinycells  # noqa: E402


@pytest.fixture(scope="module")
def cells(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("replay"))
    tinycells.write_cell(tmp, tinycells.limits_of("paper12.replay"))
    return tmp


@pytest.fixture
def fresh_engine():
    """Faults are planted in functions the engine traces: drop every
    compiled program before and after, so none outlives its fault."""
    from repro.cluster import state as cstate

    cstate._ENGINE_CACHE.clear()
    jax.clear_caches()
    yield
    cstate._ENGINE_CACHE.clear()
    jax.clear_caches()


def test_sound_replay_is_correct(cells):
    out = tinycells.run(cells, "tiny.replay")
    assert out["correct"], out["checks"]
    assert out["checks"]["rt_stat_gap"]["value"] < 1e-5
    assert out["metrics"]["replay_node_ticks_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"


def test_bfloat16_control_fails(cells):
    from chipbench import harness

    cell = tinycells.load(cells, "tiny.replay")
    driver = harness.load_module("drivers", "replay")
    ctx = driver.setup(cell, warm=False)
    records = [driver.call(ctx, 0)]
    driver.release(ctx)
    control = driver.check(ctx, driver.as_reference(ctx, records,
                                                    jnp.bfloat16))
    limits = cell.workload["limits"]
    assert any(control[k] > limits[k] for k in limits), control


def _state_unchanged(monkeypatch):
    from repro.cluster import state as cstate

    orig = cstate._tick

    def tick(st, *args):
        return st, orig(st, *args)[1]

    monkeypatch.setattr(cstate, "_tick", tick)


def _half_batch(monkeypatch):
    from repro.cluster import state as cstate

    orig = cstate.batched_rollout

    def batched(state, profiles, t0, keys, events, **kw):
        half = keys.shape[0] // 2
        final, outs = orig(state, profiles, t0, keys[:half], events, **kw)
        twice = lambda x: jnp.concatenate([x, x])  # noqa: E731
        return (jax.tree.map(twice, final), jax.tree.map(twice, outs))

    monkeypatch.setattr(cstate, "batched_rollout", batched)


def _answer_altered(monkeypatch):
    from repro.cluster import experiment

    orig = experiment.replay_plan_batched

    def replay(*args, **kw):
        out = orig(*args, **kw)
        out["seeds"][-1]["p99_rt"] *= 1.001
        return out

    monkeypatch.setattr(experiment, "replay_plan_batched", replay)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
def test_fault_is_not_correct(cells, fresh_engine, monkeypatch, fault):
    fault(monkeypatch)
    out = tinycells.run(cells, "tiny.replay")
    assert not out["correct"], out["checks"]


def test_reference_agrees_on_hot_windows_and_spread(cells):
    from chipbench import harness
    from chipbench.reference import sim as ref

    cell = tinycells.load(cells, "tiny.replay", seed=11)
    driver = harness.load_module("drivers", "replay")
    ctx = driver.setup(cell, warm=False)
    rec = driver.call(ctx, 0)
    want = ref.replay_stats(cell.config, ctx["plan"], rec["sim_seeds"])
    got = {s["sim_seed"]: s for s in rec["seeds"]}
    for w in want["seeds"]:
        g = got[w["sim_seed"]]
        assert g["hot_windows"] == w["hot_windows"]
        np.testing.assert_allclose(g["cpu_util_std"], w["cpu_util_std"],
                                   rtol=1e-5)
    assert want["invalid"] == 0
