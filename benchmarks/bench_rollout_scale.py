"""Rollout-engine throughput: single-device vmap vs device-sharded shard_map.

Replays a synthetic placement plan (a stable online fleet plus recurring
offline waves — the same shape as the mitigation traces, but generated
directly as an ``extract_plan`` log so a 1k-node scenario does not need a
1k-node ``run_experiment``) across a 20-seed batch, through both engines
of ``state.batched_rollout``:

* ``vmap`` — the single-device batched scan (the PR-6 core), and
* ``shard`` — the same vmapped scan wrapped in ``shard_map`` over a 1-D
  "seeds" mesh of host devices (``--devices N`` forces N virtual CPU
  devices via ``XLA_FLAGS=--xla_force_host_platform_device_count``, set
  before jax imports — which is why this module imports everything lazily).

Grid: {3-day, 7-day} x {12, 1k} nodes.  The 12-node rows run their full
span; the 1k-node rows replay a time-scaled sample of the same trace
(full-span 1k-node rollouts cost hours of CPU — the per-node-tick
throughput is the scale-comparable number, and the row is marked
``scaled_sample``).  Each engine row reports cold (includes compile) and
warm wall, windows/sec and node-ticks/sec from the warm wall.

The gated row is the 20-seed 3-day 12-node replay: ``gate.speedup`` is
warm-vmap / warm-shard, ``gate.parity_rel_diff`` the worst per-seed p99
relative difference between the two engines (expected 0.0 — sharding a
seed-independent batch is bitwise).  CI asserts speedup >= 2x on 4 host
devices and parity <= 1e-5 from the ``--json`` artifact
(``BENCH_rollout_scale.json``).
"""
from __future__ import annotations

import json
import os
import sys
import time

SIM_SEEDS = tuple(range(20))
WINDOW_TICKS = 40
TICKS_PER_DAY = 2880
SAMPLE_SEEDS = (0, 1)      # seed axis for the scaled 1k-node sample rows


def _synthetic_plan(num_nodes: int, days: float, seed: int = 0):
    """A mutation log shaped like the bursty mitigation traces: two online
    services per node at t=0, then offline waves every ~160 ticks that
    expire on their own.  Returns (log, t_end)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    t_end = int(days * TICKS_PER_DAY)
    log = []
    num_types = 4  # len(workloads.ONLINE_NAMES); kept literal to stay lazy
    for node in range(num_nodes):
        for slot in (0, 1):
            log.append(("place_on", 0.0, node, slot,
                        int(rng.integers(0, num_types)),
                        float(rng.uniform(180, 420)),
                        float(rng.uniform(0, 6.28))))
    t, wave = 160, 0
    while t < t_end - 10:
        for j in range(4):  # one wave = 4 co-scheduled jobs
            node = int((wave * 7 + j * 3) % num_nodes)
            log.append(("place_off", float(t), node, j % 6,
                        2.0, 4.0, 8.0, float(rng.uniform(1.2, 2.1)),
                        int(rng.integers(120, 240))))
        wave += 1
        t += int(rng.integers(140, 200))
    return log, t_end


def _build_scenario(num_nodes: int, days: float):
    import jax.numpy as jnp

    from repro.cluster import state as cstate
    from repro.cluster import workloads as W

    log, t_end = _synthetic_plan(num_nodes, days)
    cpw = max(1, WINDOW_TICKS // cstate.CHUNK)
    num_windows = -(-(t_end // cstate.CHUNK) // cpw)
    events = cstate.extract_plan(log, 0.0, num_windows, cpw)
    seeds = SIM_SEEDS if num_nodes <= 100 else SAMPLE_SEEDS
    keys = cstate.replay_key_stream(cstate.seed_keys(seeds), num_windows,
                                    cpw)
    state0 = cstate.ClusterState.create(num_nodes)
    profiles = {k: jnp.asarray(v) for k, v in W.online_arrays().items()}
    return dict(state0=state0, profiles=profiles, keys=keys, events=events,
                seeds=seeds, num_windows=num_windows, t_end=t_end,
                num_nodes=num_nodes, days=days)


def _seed_p99(rt, t_end):
    """Per-seed p99 over the driver's sampling span (warmup < 30 skipped)."""
    import numpy as np

    span = rt.shape[1] * rt.shape[2]
    tick = np.arange(span).reshape(rt.shape[1], rt.shape[2])
    valid = (tick >= 30) & (tick < t_end)
    out = []
    for i in range(rt.shape[0]):
        s = rt[i][valid]
        s = s[s > 0]
        out.append(float(np.percentile(s, 99)) if s.size else float("nan"))
    return out


def _time_engine(sc, devices):
    import jax
    import numpy as np

    from repro.cluster import state as cstate

    def once():
        t0 = time.time()
        _, outs = cstate.batched_rollout(
            sc["state0"], sc["profiles"], 0.0, sc["keys"], sc["events"],
            devices=devices)
        jax.block_until_ready(outs["rt"])
        return time.time() - t0, outs

    cold, _ = once()
    warm, outs = once()
    rt = np.asarray(outs["rt"])
    b, w = rt.shape[0], rt.shape[1]
    ticks = w * rt.shape[2]
    return {
        "cold_s": round(cold, 3),
        "warm_s": round(warm, 3),
        "windows_per_s": round(b * w / warm, 2),
        "node_ticks_per_s": round(b * ticks * sc["num_nodes"] / warm, 1),
    }, _seed_p99(rt, sc["t_end"])


def run(fast: bool = True, json_path: str | None = None,
        devices: int | None = None):
    import jax

    # asking for more devices than exist raises in batched_rollout
    ndev = jax.device_count() if devices is None else devices

    grid = [(3.0, 12)]
    if not fast:
        grid.append((7.0, 12))
    # 1k-node rows: time-scaled samples (marked), per-node-tick comparable
    samples = [(0.1, 1000)] if fast else [(0.1, 1000), (0.25, 1000)]

    out, rows, gate = [], [], None
    for days, nodes in grid + samples:
        sc = _build_scenario(nodes, days)
        scaled = nodes > 100
        vmap_row, vmap_p99 = _time_engine(sc, devices=None)
        shard_row, shard_p99 = _time_engine(sc, devices=ndev)
        diffs = [abs(a - b) / b for a, b in zip(shard_p99, vmap_p99) if b]
        parity = max(diffs) if diffs else float("nan")
        speedup = vmap_row["warm_s"] / shard_row["warm_s"]
        label = f"{days:g}day_{nodes}n"
        for eng, row in (("vmap", vmap_row), ("shard", shard_row)):
            rows.append({
                "scenario": label, "engine": eng, "days": days,
                "nodes": nodes, "seeds": len(sc["seeds"]),
                "windows": sc["num_windows"], "scaled_sample": scaled,
                **row,
            })
            out.append((
                f"rollout_scale_{label}_{eng}",
                row["warm_s"] * 1e6,
                f"windows_per_s={row['windows_per_s']};"
                f"node_ticks_per_s={row['node_ticks_per_s']};"
                f"devices={1 if eng == 'vmap' else ndev}",
            ))
        if (days, nodes) == (3.0, 12):
            gate = {"scenario": label, "devices": ndev,
                    "seeds": len(sc["seeds"]),
                    "speedup": round(speedup, 3),
                    "parity_rel_diff": parity}
        out.append((
            f"rollout_scale_{label}_speedup", 0.0,
            f"speedup={speedup:.2f};parity_rel_diff={parity:.2e}",
        ))

    doc = {"devices": ndev, "backend": jax.default_backend(),
           "fast": fast, "rows": rows, "gate": gate}
    if json_path:
        with open(json_path, "w") as f:
            json.dump(doc, f, indent=2)
    return out


def _flag_value(argv, flag, default):
    if flag not in argv:
        return None
    i = argv.index(flag)
    if i + 1 < len(argv) and not argv[i + 1].startswith("--"):
        return argv[i + 1]
    return default


def main():
    # --devices N must take effect before jax initializes: append the
    # host-device override to XLA_FLAGS while no jax import has happened
    # (this module and its helpers import jax lazily for exactly this)
    devices = _flag_value(sys.argv, "--devices", "4")
    if devices is not None:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={int(devices)}")
    from repro.launch.cache import enable_persistent_cache

    enable_persistent_cache()
    json_path = _flag_value(sys.argv, "--json", "BENCH_rollout_scale.json")
    for row in run(fast="--full" not in sys.argv, json_path=json_path,
                   devices=int(devices) if devices else None):
        print(",".join(map(str, row)))


if __name__ == "__main__":
    main()
