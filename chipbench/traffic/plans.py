"""Seeded placement/action plans for the replay cells.

A plan is what ``replay_plan_batched`` replays: a mutation log of host
tuples in the simulator's event vocabulary plus the trace geometry.  This
generator grew out of the repository's ``_synthetic_plan``
(``benchmarks/bench_rollout_scale.py``: online services placed per node,
then offline jobs that expire on their own) and adds what a deployment
plan needs: a ramp of online starts, Poisson or wave arrivals of batch
jobs into free offline slots, and mitigation events (migrate, evict,
resize) at fixed counts on live targets.  Every parameter comes from the
workload's data file; the same seed always gives the same plan.

Log tuples (the simulator's ``Cluster.log`` format):

* ``("place_on", t, node, slot, type_id, qps, phase)``
* ``("place_off", t, node, slot, cores, threads, mem, burst, remaining)``
* ``("evict_off", t, node, slot)``
* ``("migrate_on", t, src, src_slot, dst, dst_slot)``
* ``("resize_on", t, node, slot, qps)``
* ``("resize_off", t, node, slot, cores, threads, mem, 0.0, remaining)``
"""
from __future__ import annotations

import numpy as np

CHUNK = 10           # ticks per simulator chunk: events land on chunk starts
TICKS_PER_DAY = 2880


def _offline_job(rng, prof: dict, size: str, duration):
    """(cores, threads, mem, burst, remaining) of one batch job."""
    choices = prof["cores_choices"]
    cores = float(choices[-2] if size == "mid" else rng.choice(choices))
    burst = float(rng.uniform(*prof["burst_range"]))
    return (cores, cores * prof["threads_per_core"],
            cores * prof["mem_per_core"], burst,
            int(rng.integers(*duration)))


def _batch_times(rng, batch: dict, t_end: int) -> list[int]:
    """Arrival chunk-start ticks of the batch jobs, one entry per job."""
    times = []
    if batch["arrival"] == "poisson":
        rate = batch["jobs_per_tick"] * CHUNK
        for t in range(batch.get("start", 0), t_end - CHUNK, CHUNK):
            times += [t] * int(rng.poisson(rate))
    elif batch["arrival"] == "waves":
        t = batch["first_wave"]
        while t < t_end - CHUNK:
            times += [t - t % CHUNK] * batch["jobs_per_wave"]
            t += int(rng.integers(*batch["wave_gap"]))
    else:
        raise ValueError(f"unknown batch arrival kind {batch['arrival']!r}")
    return times


def make_plan(config: dict, traffic: dict, seed: int) -> dict:
    """The ``replay_plan_batched`` plan of one deployment under one mix.

    ``config`` is a configuration file's dict (``nodes``, slot counts,
    ``profiles``); ``traffic`` the workload's ``plan`` parameters.
    Returns ``{"log", "t_end", "num_nodes", "settle_ticks"}``.
    """
    rng = np.random.default_rng(seed)
    n = config["nodes"]
    s_on, s_off = config["online_slots"], config["offline_slots"]
    online = config["profiles"]["online"]
    offline = config["profiles"]["offline"]
    off_names = sorted(offline)
    t_end = int(round(traffic["days"] * TICKS_PER_DAY))

    # occupancy the generator tracks so every event hits a live target
    on_busy = np.zeros((n, s_on), bool)
    off_until = np.zeros((n, s_off), np.int64)   # tick the job expires
    off_size = np.zeros((n, s_off, 3))           # its cores, threads, mem

    pending: dict[int, list[tuple]] = {}

    def at(t: int, entry: tuple) -> None:
        pending.setdefault(t, []).append(entry)

    # online services: `per_node` each (or `total` over the fleet), each
    # starting at a chunk drawn from the ramp
    on_cfg = traffic["online"]
    ramp = max(on_cfg.get("ramp_ticks", 0), 1)
    total = on_cfg.get("total", on_cfg.get("per_node", 0) * n)
    on_types = sorted(online, key=lambda k: online[k]["type_id"])
    for i in range(total):
        node = i % n if "per_node" in on_cfg else int(rng.integers(n))
        t = int(rng.integers(0, ramp)) // CHUNK * CHUNK
        name = on_types[int(rng.integers(len(on_types)))]
        at(t, ("on", node, online[name]["type_id"],
               float(rng.uniform(*on_cfg["qps"])),
               float(rng.uniform(0.0, 2 * np.pi))))

    batch = traffic["batch"]
    for t in _batch_times(rng, batch, t_end):
        at(t, ("off", int(rng.integers(n)),
               off_names[int(rng.integers(len(off_names)))]))

    mitig = traffic.get("mitigations", {})
    lo = traffic.get("mitigation_start", 0) // CHUNK
    for kind in sorted(mitig):
        for _ in range(mitig[kind]):
            at(int(rng.integers(lo, t_end // CHUNK)) * CHUNK, (kind,))

    log: list[tuple] = []
    dropped = 0
    for t in sorted(pending):
        freed = off_until <= t
        for entry in pending[t]:
            kind = entry[0]
            if kind == "on":
                _, node, type_id, qps, phase = entry
                free = np.flatnonzero(~on_busy[node])
                if free.size == 0:
                    node_free = np.flatnonzero(~on_busy.all(1))
                    if node_free.size == 0:
                        dropped += 1
                        continue
                    node = int(node_free[rng.integers(node_free.size)])
                    free = np.flatnonzero(~on_busy[node])
                s = int(free[0])
                on_busy[node, s] = True
                log.append(("place_on", float(t), node, s, type_id, qps, phase))
            elif kind == "off":
                _, node, name = entry
                free = np.flatnonzero(freed[node])
                if free.size == 0:
                    dropped += 1
                    continue
                s = int(free[0])
                job = _offline_job(rng, offline[name], batch["size"],
                                   batch["duration"])
                off_until[node, s] = t + job[4]
                off_size[node, s] = job[:3]
                freed[node, s] = False
                log.append(("place_off", float(t), node, s) + job)
            else:
                entry = _mitigation(rng, kind, t, on_busy, off_until, off_size,
                                    freed)
                if entry is None:
                    dropped += 1
                    continue
                log.append(entry)
    return {"log": log, "t_end": float(t_end), "num_nodes": n,
            "settle_ticks": traffic.get("settle_ticks", 40),
            "dropped": dropped}


def _mitigation(rng, kind, t, on_busy, off_until, off_size, freed):
    """One mitigation event on a live target at tick t (None when none)."""
    if kind == "migrate_on":
        src = np.argwhere(on_busy)
        dst_nodes = np.flatnonzero(~on_busy.all(1))
        if len(src) == 0 or dst_nodes.size == 0:
            return None
        node, s = (int(v) for v in src[rng.integers(len(src))])
        dst_nodes = dst_nodes[dst_nodes != node]
        if dst_nodes.size == 0:
            return None
        dst = int(dst_nodes[rng.integers(dst_nodes.size)])
        ds = int(np.flatnonzero(~on_busy[dst])[0])
        on_busy[node, s], on_busy[dst, ds] = False, True
        return ("migrate_on", float(t), node, s, dst, ds)
    if kind == "resize_on":
        live = np.argwhere(on_busy)
        if len(live) == 0:
            return None
        node, s = (int(v) for v in live[rng.integers(len(live))])
        return ("resize_on", float(t), node, s,
                float(rng.uniform(120.0, 500.0)))
    live = np.argwhere(~freed)
    if len(live) == 0:
        return None
    node, s = (int(v) for v in live[rng.integers(len(live))])
    if kind == "evict_off":
        off_until[node, s] = t
        freed[node, s] = True
        return ("evict_off", float(t), node, s)
    if kind == "resize_off":
        # throttle to a fraction of the cores, work conserved: the job
        # runs proportionally longer (the simulator's own resize rule)
        ratio = float(rng.uniform(0.4, 0.8))
        rem = max(int(round((off_until[node, s] - t) / ratio)), 1)
        off_until[node, s] = t + rem
        off_size[node, s] *= ratio
        cores, threads, mem = (float(v) for v in off_size[node, s])
        return ("resize_off", float(t), node, s, cores, threads, mem, 0.0,
                rem)
    raise ValueError(f"unknown mitigation kind {kind!r}")
