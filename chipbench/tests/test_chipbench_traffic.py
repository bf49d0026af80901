"""The traffic generator is deterministic per seed, every event of its
plans applies, and the configuration files state what the program runs."""
from __future__ import annotations

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import pytest  # noqa: E402

from chipbench import harness  # noqa: E402
from chipbench.reference import sim as ref  # noqa: E402
from chipbench.tests import tinycells  # noqa: F401,E402  (src on the path)
from chipbench.traffic import plans  # noqa: E402


def _names(kind):
    return sorted(f[:-5] for f in os.listdir(os.path.join(harness.HERE, kind))
                  if f.endswith(".json"))


def _config(name):
    with open(os.path.join(harness.HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def _workload(name):
    with open(os.path.join(harness.HERE, "workloads", f"{name}.json")) as f:
        return json.load(f)


REPLAY_CELLS = [w for w in _names("workloads")
                if _workload(w)["driver"] == "replay"]
# the generator's other arrival kind, on the same cells
POISSON = {"arrival": "poisson", "jobs_per_tick": 0.2, "size": "any",
           "duration": [40, 80]}


def _small(cell, batch):
    """The cell's plan parameters on a few nodes for a short span, with
    its own batch arrivals or ``batch``."""
    wl = _workload(cell)
    cfg = dict(_config(wl["config"]), nodes=40)
    plan = dict(wl["plan"], days=0.2)
    if batch is not None:
        plan["batch"] = batch
    elif plan["batch"]["arrival"] == "poisson":
        plan["batch"] = dict(plan["batch"], jobs_per_tick=0.2)
    return cfg, plan


BATCH = pytest.mark.parametrize("batch", [None, POISSON],
                                ids=["own", "poisson"])


@BATCH
@pytest.mark.parametrize("cell", REPLAY_CELLS)
def test_plans_are_deterministic_per_seed(cell, batch):
    cfg, plan = _small(cell, batch)
    a = plans.make_plan(cfg, plan, 2**31 + 3)
    assert a == plans.make_plan(cfg, plan, 2**31 + 3)
    assert a["log"] != plans.make_plan(cfg, plan, 2**31 + 4)["log"]


@BATCH
@pytest.mark.parametrize("cell", REPLAY_CELLS)
def test_plan_events_all_apply(cell, batch):
    cfg, plan = _small(cell, batch)
    p = plans.make_plan(cfg, plan, 7)
    state = ref.State(cfg)
    t = 0
    for e in p["log"]:
        while t < e[1]:
            state.age(plans.CHUNK)
            t += plans.CHUNK
        state.apply(e)
    assert {e[0] for e in p["log"]} >= {"place_on", "place_off"}


@pytest.mark.parametrize("config", _names("configs"))
def test_configuration_states_the_program_tables(config):
    from repro.cluster import workloads as W
    from repro.cluster.fleet import MACHINE_CLASSES
    from repro.cluster.state import S_OFF, S_ON
    from repro.control.detector import DetectorConfig

    cfg = _config(config)
    for name, prof in cfg["profiles"]["online"].items():
        prog = dataclasses.asdict(W.ONLINE_PROFILES[name])
        assert {k: prog[k] for k in prof} == prof
    for name, prof in cfg["profiles"]["offline"].items():
        prog = dataclasses.asdict(W.OFFLINE_PROFILES[name])
        assert {k: list(v) if isinstance(v, tuple) else v
                for k, v in prog.items() if k in prof} == prof
    mc = cfg["machine_class"]
    assert dataclasses.asdict(MACHINE_CLASSES[mc["name"]]) == mc
    det = dataclasses.asdict(DetectorConfig())
    assert {k: det[k] for k in cfg["detector"]} == cfg["detector"]
    assert (cfg["online_slots"], cfg["offline_slots"]) == (S_ON, S_OFF)
