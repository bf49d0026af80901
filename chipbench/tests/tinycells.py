"""A tiny cell for the CPU tests: the real replay driver, the
configuration cut to a few nodes and a fraction of a day, written to a
temporary directory so that the harness finds it by name like any other
cell."""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness  # noqa: E402

CONFIG = "paper-testbed-12"

REPLAY = {
    "config": "tiny-testbed", "driver": "replay", "traffic": "tiny-plan",
    "chips": 1, "why": "CPU test", "seeds_per_call": 4, "window_ticks": 40,
    "plan": {"days": 0.125,
             "online": {"total": 10, "qps": [120, 500], "ramp_ticks": 30},
             "batch": {"arrival": "waves", "jobs_per_wave": 3,
                       "wave_gap": [40, 60], "first_wave": 40,
                       "size": "mid", "duration": [40, 80]},
             "mitigations": {"migrate_on": 2, "evict_off": 2,
                             "resize_off": 2, "resize_on": 1},
             "mitigation_start": 40, "settle_ticks": 40},
}


def write_cell(tmp: str, limits: dict) -> None:
    """tiny.replay, held to ``limits``, and its configuration under
    ``tmp``."""
    with open(os.path.join(HERE, "..", "configs", f"{CONFIG}.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny-testbed", nodes=6)
    os.makedirs(os.path.join(tmp, "configs"), exist_ok=True)
    os.makedirs(os.path.join(tmp, "workloads"), exist_ok=True)
    with open(os.path.join(tmp, "configs", "tiny-testbed.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(tmp, "workloads", "tiny.replay.json"), "w") as f:
        json.dump(dict(REPLAY, limits=limits), f)


def limits_of(cell: str) -> dict:
    """The limits a committed cell holds, to test the tiny cell against."""
    with open(os.path.join(HERE, "..", "workloads", f"{cell}.json")) as f:
        return json.load(f)["limits"]


def load(tmp: str, name: str, seed: int = 2**31 + 5):
    return harness.load_cell(name, seed,
                             workload_dirs=[os.path.join(tmp, "workloads")],
                             config_dirs=[os.path.join(tmp, "configs")])


def run(tmp: str, name: str, seconds: float = 0.01, trace: bool = False,
        seed: int = 2**31 + 5) -> dict:
    """A whole run of the tiny cell on the CPU: everything but the look for
    a chip."""
    import jax

    cell = load(tmp, name, seed)
    return harness.run_cell(cell, seconds, trace, jax.devices(),
                            time.perf_counter(),
                            trace_dir=os.path.join(tmp, "trace"))
