"""The live driver against the plain admission and control reference, at a
tiny size on the CPU: sound calls are correct, the control and each
planted fault are not, and the live configuration is the testbed's in
every section the two share."""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import harness  # noqa: E402
from chipbench.tests import tinycells  # noqa: F401,E402  (src on the path)

LIVE = "paper-testbed-12-live"
# the testbed's tables, which the live configuration repeats: the loader
# tests hold every configuration file to the program's full tables
SHARED = ("nodes", "machine_class", "fleet", "online_slots",
          "offline_slots", "reduced", "model", "detector", "profiles")
# what makes the live deployment another one: the part of the paper it
# runs (its source), its policy, what the driver runs, the guarantees its
# run is held to, and their description
OWN = ("name", "source", "deployment", "assumed", "guarantees", "admission",
       "control")


def _config(name):
    with open(os.path.join(harness.HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def _workload(name):
    with open(os.path.join(harness.HERE, "workloads", f"{name}.json")) as f:
        return json.load(f)


def test_live_configuration_is_the_testbed_in_every_shared_section():
    live, testbed = _config(LIVE), _config(_workload("paper12.replay")[
        "config"])
    for key in SHARED:
        assert live[key] == testbed[key], key
    assert set(live) == set(SHARED) | set(OWN)
    assert set(testbed) <= set(live)
    assert live["assumed"][:len(testbed["assumed"])] == testbed["assumed"]


def _write_tiny(tmp: str) -> None:
    cfg = _config(LIVE)
    cfg.update(name="tiny-live", nodes=6)
    cfg["admission"]["predictor"]["training_placements"] = 40
    wl = dict(_workload("paper12.live"), config="tiny-live", trace={
        "num_online": 6, "num_bursts": 4, "jobs_per_burst": 3,
        "burst_gap": [40, 60], "job_duration": [60, 120]})
    for kind, name, data in (("configs", "tiny-live", cfg),
                             ("workloads", "tiny.live", wl)):
        os.makedirs(os.path.join(tmp, kind), exist_ok=True)
        with open(os.path.join(tmp, kind, f"{name}.json"), "w") as f:
            json.dump(data, f)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("live"))
    _write_tiny(tmp)
    return tmp


@pytest.fixture(scope="module")
def live(tiny):
    """The tiny cell's driver, set up once (its random forest trained)."""
    cell = tinycells.load(tiny, "tiny.live")
    driver = harness.load_module("drivers", "live")
    ctx = driver.setup(cell, warm=False)
    yield cell, driver, ctx
    driver.release(ctx)


def _correct(cell, numbers) -> bool:
    limits = cell.workload["limits"]
    return all(np.isfinite(v) and v <= limits[k] for k, v in numbers.items())


def test_sound_run_is_correct_through_the_harness(tiny):
    out = harness.run_cell(tinycells.load(tiny, "tiny.live", seed=2**31 + 9),
                           0.01, False, jax.devices(), time.perf_counter(),
                           trace_dir=os.path.join(tiny, "trace"))
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == set(_workload("paper12.live")["limits"])
    assert out["metrics"]["replay_node_ticks_per_s"]["value"] > 0
    assert out["attempted"] > 0 and out["failed"] == 0


def test_sound_calls_are_correct_over_seeds(live):
    cell, driver, ctx = live
    records = [driver.call(ctx, i) for i in range(3)]
    assert len({(r["trace_seed"], r["sim_seed"]) for r in records}) == 3
    numbers = driver.check(ctx, records)
    assert _correct(cell, numbers), numbers
    # the cell exercises what it checks: flags, actions and guards
    flagged = [f for r in records for o in r["outcomes"]
               for f in o["flagged"]]
    assert any(f["guard"] is None for f in flagged)
    assert any(f["guard"] is not None for f in flagged)
    assert sum(len(r["decisions"]) for r in records) == sum(
        r["offers"] for r in records)


def test_bfloat16_control_fails(live):
    cell, driver, ctx = live
    records = [driver.call(ctx, 0)]
    control = driver.check(ctx, driver.as_reference(ctx, records,
                                                    jnp.bfloat16))
    assert not _correct(cell, control), control


def _first_feasible(monkeypatch):
    """A scheduler that takes the first feasible node, not the best."""
    from repro.core import scheduler

    orig = scheduler._score_nodes

    def first(*args):
        best, score = orig(*args)
        ok = jnp.isfinite(score)
        return jnp.where(ok.any(), jnp.argmax(ok), -1), score

    monkeypatch.setattr(scheduler, "_score_nodes", first)


def _never_mitigates(monkeypatch):
    """A loop that never acts and says no candidate was there."""
    from repro.control.policy import MitigationPolicy

    def plan(self, cluster, view, hot, *args, declined=None, **kw):
        if declined is not None:
            declined.update({int(n): {"guard": "no_candidate"}
                             for n in np.nonzero(hot)[0]})
        return []

    monkeypatch.setattr(MitigationPolicy, "plan", plan)


def _eq1_high(monkeypatch):
    """Eq. 1's node interference read 1% high."""
    from repro.core.interference import InterferenceQuantifier

    orig = InterferenceQuantifier.intf_nodes
    monkeypatch.setattr(InterferenceQuantifier, "intf_nodes",
                        lambda self, *a: orig(self, *a) * 1.01)


@pytest.mark.parametrize("fault,number", [
    (_first_feasible, "admit_choice_gap"),
    (_never_mitigates, "unhandled_hot_nodes"),
    (_eq1_high, "admit_terms_gap")])
def test_fault_is_not_correct(live, monkeypatch, fault, number):
    cell, driver, ctx = live
    fault(monkeypatch)
    records = [driver.call(ctx, 0)]
    monkeypatch.undo()
    numbers = driver.check(ctx, records)
    assert numbers[number] > cell.workload["limits"][number], numbers
