"""Plain reference of the cluster simulation that the timed paths run.

It imports nothing of the program.  From a configuration file (machine
physics, service profiles, detector settings), a mutation log and a list
of simulation seeds it recomputes, in straightforward ``jax.numpy``:

* the tick: diurnal QPS with noise, per-node CPU/thread/memory load, the
  M/G/1-PS delay curve with thread oversubscription and log-normal jitter,
  per-pod Erlang(2) runqlat draws, and online response times;
* the 200-bin runqlat histogram of every node, and the runqlat detector's
  node track (decayed histogram, EWMA baseline, CUSUM, tail quantile);
* the host reduction of a replay: per-seed avg/p90/p99 RT over ticks
  [30, t_end), window-level CPU/memory utilization spread, hot windows.

The random stream is part of the simulation's semantics: a seed's chunk
keys are successive splits of ``PRNGKey(seed)``, a chunk's tick keys a
10-way split of its key, and every draw is folded from the tick key in a
fixed way.  The reference draws the same stream, so a correct program
agrees with it up to float rounding.

State evolution does not depend on the draws (offline jobs only count
down), so the state is shared by all seeds and events are applied on the
host between chunks; the draws and the telemetry are vmapped over seeds.

``dtype`` selects the arithmetic: ``float32`` is the configuration's
precision, ``bfloat16`` the control (the draws stay float32 and are
rounded to it, everything after is computed in it).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

WARMUP_TICKS = 30     # the drivers' RT pool starts here


class InvalidEvent(ValueError):
    """A mutation the simulation cannot apply: its target slot is not in
    the state the event claims."""


class State:
    """Host copy of the cluster arrays (shared by every seed)."""

    def __init__(self, config: dict):
        n = config["nodes"]
        s_on, s_off = config["online_slots"], config["offline_slots"]
        mc = config["machine_class"]
        self.on_active = np.zeros((n, s_on), bool)
        self.on_type = np.zeros((n, s_on), np.int32)
        self.on_qps = np.zeros((n, s_on), np.float32)
        self.on_phase = np.zeros((n, s_on), np.float32)
        self.off_active = np.zeros((n, s_off), bool)
        self.off_cores = np.zeros((n, s_off), np.float32)
        self.off_threads = np.zeros((n, s_off), np.float32)
        self.off_mem = np.zeros((n, s_off), np.float32)
        self.off_burst = np.ones((n, s_off), np.float32)
        self.off_remaining = np.zeros((n, s_off), np.int32)
        self.cores = np.full(n, mc["cores"], np.float32)
        self.mem = np.full(n, mc["mem_gb"], np.float32)

    def apply(self, e: tuple) -> None:
        """Apply one log entry; raise ``InvalidEvent`` on a bad target."""
        op = e[0]
        n = self.on_active.shape[0]
        nodes = (e[2], e[4]) if op.startswith("migrate") else (e[2],)
        if not all(0 <= int(v) < n for v in nodes):
            raise InvalidEvent(f"node out of range: {e}")
        node, slot = int(e[2]), int(e[3])
        if op == "place_on":
            self._need(not self.on_active[node, slot], e)
            self.on_active[node, slot] = True
            self.on_type[node, slot] = int(e[4])
            self.on_qps[node, slot] = e[5]
            self.on_phase[node, slot] = e[6]
        elif op == "place_off":
            self._need(not self.off_active[node, slot], e)
            self.off_active[node, slot] = True
            (self.off_cores[node, slot], self.off_threads[node, slot],
             self.off_mem[node, slot], self.off_burst[node, slot]) = e[4:8]
            self.off_remaining[node, slot] = int(e[8])
        elif op == "evict_on":
            self._need(self.on_active[node, slot], e)
            self._clear_on(node, slot)
        elif op == "evict_off":
            self._need(self.off_active[node, slot], e)
            self._clear_off(node, slot)
        elif op in ("migrate_on", "migrate_off"):
            dst, ds = int(e[4]), int(e[5])
            kind = op[-2:] if op.endswith("on") else "off"
            active = self.on_active if kind == "on" else self.off_active
            self._need(active[node, slot] and not active[dst, ds], e)
            names = (("on_active", "on_type", "on_qps", "on_phase")
                     if kind == "on" else
                     ("off_active", "off_cores", "off_threads", "off_mem",
                      "off_burst", "off_remaining"))
            for name in names:
                a = getattr(self, name)
                a[dst, ds] = a[node, slot]
            if kind == "on":
                self._clear_on(node, slot)
            else:
                self._clear_off(node, slot)
        elif op == "resize_on":
            self._need(self.on_active[node, slot], e)
            self.on_qps[node, slot] = e[4]
        elif op == "resize_off":
            self._need(self.off_active[node, slot], e)
            (self.off_cores[node, slot], self.off_threads[node, slot],
             self.off_mem[node, slot]) = e[4:7]
            self.off_remaining[node, slot] = int(e[8])
        else:
            raise InvalidEvent(f"unknown event {op!r}")

    @staticmethod
    def _need(ok, e) -> None:
        if not ok:
            raise InvalidEvent(f"event does not match the state: {e}")

    def _clear_on(self, node, slot) -> None:
        self.on_active[node, slot] = False
        self.on_type[node, slot] = 0
        self.on_qps[node, slot] = 0.0
        self.on_phase[node, slot] = 0.0

    def _clear_off(self, node, slot) -> None:
        self.off_active[node, slot] = False
        self.off_cores[node, slot] = 0.0
        self.off_threads[node, slot] = 0.0
        self.off_mem[node, slot] = 0.0
        self.off_burst[node, slot] = 1.0
        self.off_remaining[node, slot] = 0

    def age(self, ticks: int) -> None:
        """Offline jobs count down one per tick and stop at zero."""
        act = self.off_active
        left = self.off_remaining.astype(np.int64)
        self.off_remaining = np.where(act, np.maximum(left - ticks, 0),
                                      left).astype(np.int32)
        self.off_active = act & (left > ticks)

    def arrays(self) -> dict:
        return {k: v.copy() for k, v in vars(self).items()}


def _constants(config: dict) -> dict:
    on = config["profiles"]["online"]
    names = sorted(on, key=lambda k: on[k]["type_id"])
    prof = {f: np.array([on[k][f] for k in names], np.float32)
            for f in ("cpu_per_qps", "cpu_base", "mem_per_qps", "mem_base",
                      "base_rt", "qps_cap", "threads_per_qps",
                      "rt_per_runqlat")}
    mc = config["machine_class"]
    phys = {f: np.float32(mc[f]) for f in ("delay_base", "delay_scale",
                                            "rho_knee", "oversub_slope")}
    return {"prof": prof, "phys": phys}


def _tick(st, prof, phys, model, t, key, dtype):
    """One tick of one seed: (rt (N, S_ON), node_hist (N, bins),
    cpu_util (N,), mem_util (N,)) for the slots active at this tick."""
    f = lambda x: jnp.asarray(x).astype(dtype)   # noqa: E731
    k_qps, k_lat, k_rt, _k_hw = jax.random.split(key, 4)
    on_act, off_act = st["on_active"], st["off_active"]
    n, s_on = on_act.shape
    typ = st["on_type"]
    day = float(model["ticks_per_day"])
    t = f(t)
    phase = f(st["on_phase"])
    season = (1.0 + f(0.35) * jnp.sin(f(2 * np.pi) * t / f(day) + phase)
              + f(0.12) * jnp.sin(f(4 * np.pi) * t / f(day) + f(1.7) * phase))
    noise = f(1.0) + f(0.06) * f(jax.random.normal(k_qps, (n, s_on)))
    qps = jnp.where(on_act, jnp.maximum(f(st["on_qps"]) * season * noise,
                                        f(0.0)), f(0.0))
    p = {k: f(v)[typ] for k, v in prof.items()}
    cpu_on = jnp.where(on_act, p["cpu_per_qps"] * qps + p["cpu_base"], f(0))
    thr_on = jnp.where(on_act, p["threads_per_qps"] * qps, f(0))
    mem_on = jnp.where(on_act, p["mem_per_qps"] * qps + p["mem_base"], f(0))
    cpu_off = jnp.where(off_act, f(st["off_cores"]), f(0))
    thr_off = jnp.where(off_act, f(st["off_threads"]), f(0))
    mem_off = jnp.where(off_act, f(st["off_mem"]), f(0))
    burst = jnp.where(off_act, f(st["off_burst"]), f(0))
    cores, mem = f(st["cores"]), f(st["mem"])
    base_cores = f(model["os_base_cores"])
    total_cpu = cpu_on.sum(-1) + cpu_off.sum(-1) + base_cores
    pressure = cpu_on.sum(-1) + (cpu_off * burst).sum(-1) + base_cores
    rho_p = pressure / cores
    threads = thr_on.sum(-1) + thr_off.sum(-1) + f(2.0)

    delay = (f(phys["delay_base"]) + f(phys["delay_scale"]) * rho_p ** 2
             / jnp.maximum(f(1.0) - rho_p, f(phys["rho_knee"])))
    delay = delay * (f(1.0) + f(phys["oversub_slope"])
                     * jnp.maximum(threads / cores - f(1.0), f(0.0)))
    delay = delay * jnp.exp(f(0.13) * f(jax.random.normal(
        jax.random.fold_in(k_lat, 99), (n,))))
    overflow = model["bin_width"] * (model["histogram_bins"] - 1)
    delay = jnp.clip(delay, f(0.0), f(2.5 * overflow))

    spt = model["samples_per_tick"]
    tiny = jnp.finfo(jnp.float32).tiny

    def samples(k, active):
        jit_ = f(1.0) + f(0.18) * f(jax.random.normal(
            jax.random.fold_in(k, 0), active.shape))
        mean = delay[:, None] * jnp.maximum(jit_, f(0.3))
        u = jax.random.uniform(jax.random.fold_in(k, 1),
                               (*active.shape, spt, 2), minval=tiny,
                               maxval=1.0)
        g = -jnp.log(f(u[..., 0]) * f(u[..., 1]))
        return g * (mean[..., None] / f(2.0)), mean

    s_on_, mean_on = samples(jax.random.fold_in(k_lat, 0), on_act)
    s_off_, _ = samples(jax.random.fold_in(k_lat, 1), off_act)
    bins = model["histogram_bins"]

    def binned(s, active):
        idx = jnp.clip(jnp.floor(s.astype(jnp.float32) / model["bin_width"]),
                       0, bins - 1).astype(jnp.int32)
        idx = jnp.where(active[..., None], idx, -1).reshape(n, -1)
        return (idx[..., None] == jnp.arange(bins)).sum(1)

    node_hist = binned(s_on_, on_act) + binned(s_off_, off_act)

    mem_used = mem_on.sum(-1) + mem_off.sum(-1) + f(2.0)
    cpu_util = jnp.minimum(total_cpu, cores) / cores
    mem_util = jnp.minimum(mem_used, mem) / mem
    sat = jnp.maximum(qps / p["qps_cap"] - f(0.8), f(0.0))
    cache = f(0.06) * p["base_rt"] * jnp.minimum(mem_used / mem,
                                                 f(1.2))[:, None]
    rt = (p["base_rt"] * (f(1.0) + f(1.5) * sat)
          + p["rt_per_runqlat"] * mean_on + cache
          + f(0.06) * p["base_rt"] * f(jax.random.normal(k_rt, (n, s_on))))
    rt = jnp.where(on_act, jnp.maximum(rt, f(0.5)), f(0.0))
    return (rt.astype(jnp.float32), node_hist.astype(jnp.float32),
            cpu_util.astype(jnp.float32), mem_util.astype(jnp.float32))


@partial(jax.jit, static_argnames=("model", "dtype"))
def _chunk(st, prof, phys, t0, chunk_keys, *, model, dtype):
    """One chunk of ``chunk_ticks`` ticks for every seed (leading axis of
    ``chunk_keys``).  Offline slots drop out at the tick their countdown
    reaches zero."""
    model = dict(model)
    ticks = model["chunk_ticks"]

    def one_seed(ck):
        keys = jax.random.split(ck, ticks)
        rts, hists, cpus, mems = [], [], [], []
        for j in range(ticks):
            stj = dict(st)
            stj["off_active"] = st["off_active"] & (st["off_remaining"] > j)
            rt, h, c, m = _tick(stj, prof, phys, model,
                                t0 + jnp.float32(j), keys[j], dtype)
            rts.append(rt)
            hists.append(h)
            cpus.append(c)
            mems.append(m)
        return (jnp.stack(rts), sum(hists[1:], hists[0]),
                jnp.stack(cpus).mean(0), jnp.stack(mems).mean(0))

    return jax.vmap(one_seed)(chunk_keys)


@partial(jax.jit, static_argnames=("num",))
def chunk_keys(seed_keys, num: int):
    """(B, num, 2) chunk keys: successive splits of each seed's key."""

    def stream(key):
        def body(k, _):
            k, sub = jax.random.split(k)
            return k, sub
        return jax.lax.scan(body, key, None, length=num)[1]

    return jax.vmap(stream)(seed_keys)


@partial(jax.jit, static_argnames=("det", "bins", "width", "dtype"))
def detector_step(carry, node_hist, *, det, bins, width, dtype):
    """The detector's node track for one window, every seed at once;
    returns (carry, hot)."""
    det = dict(det)
    f = lambda x: jnp.asarray(x).astype(dtype)   # noqa: E731
    hist, mu, cusum, steps = carry
    hist = hist * f(det["decay"]) + f(node_hist)
    k = f(np.arange(bins) * width)
    den = hist.sum(-1)
    avg = jnp.where(den > 0, (hist * k).sum(-1) / jnp.maximum(den, f(1e-12)),
                    f(0.0))
    cdf = jnp.cumsum(hist, -1) / jnp.maximum(den, f(1e-12))[..., None]
    tail = f(jnp.argmax(cdf >= f(det["quantile"] / 100.0), axis=-1) * width)
    a = f(det["baseline_alpha"])
    mu = jnp.where(steps == 0, avg, (f(1.0) - a) * mu + a * avg)
    cusum = jnp.maximum(cusum + (avg - mu - f(det["slack"])), f(0.0))
    raw = (cusum > f(det["drift_threshold"])) | (tail > f(det["abs_threshold"]))
    hot = raw & (steps >= det["warmup"])
    cusum = jnp.where(raw, f(0.0), cusum)
    return (hist, mu, cusum, steps + 1), hot


def simulate(config: dict, log, t_end: float, sim_seeds, *,
             num_chunks: int | None = None, dtype=jnp.float32) -> dict:
    """Run the reference over ``num_chunks`` chunks (default: through
    ``t_end``) for every seed.

    Returns per-seed ``rt`` samples (the pooled online RT over ticks
    [30, t_end)), window-level ``cpu_util``/``mem_util`` (B, W, N),
    per-window ``hot`` flags (B, W, N), and ``invalid``:
    the log entries the state refused (skipped, counted).
    """
    model = config["model"]
    chunk = model["chunk_ticks"]
    cpw = model["window_ticks"] // chunk
    t_end_i = int(round(t_end))
    if num_chunks is None:
        num_chunks = -(-t_end_i // chunk)
    consts = _constants(config)
    prof = {k: jnp.asarray(v) for k, v in consts["prof"].items()}
    phys = consts["phys"]
    model_key = tuple(sorted((k, v) for k, v in model.items()
                             if not isinstance(v, (list, dict))))
    seeds = jnp.stack([jax.random.PRNGKey(int(s)) for s in sim_seeds])
    keys = chunk_keys(seeds, num_chunks)
    by_chunk: dict[int, list] = {}
    for e in log:
        by_chunk.setdefault(int(e[1]) // chunk, []).append(e)

    state = State(config)
    invalid = 0
    b = len(sim_seeds)
    rts = [[] for _ in range(b)]
    cpu_w, mem_w, hot_w = [], [], []
    win_hist = win_cpu = win_mem = None
    bins, width = model["histogram_bins"], model["bin_width"]
    n = config["nodes"]
    det = tuple(sorted(config["detector"].items()))
    det_carry = (jnp.zeros((b, n, bins), dtype), jnp.zeros((b, n), dtype),
                 jnp.zeros((b, n), dtype), jnp.int32(0))
    for c in range(num_chunks):
        for e in by_chunk.get(c, ()):
            try:
                state.apply(e)
            except InvalidEvent:
                invalid += 1
        dev = {k: jnp.asarray(v) for k, v in state.arrays().items()}
        rt, hist, cpu, mem = _chunk(dev, prof, phys,
                                    jnp.float32(c * chunk), keys[:, c],
                                    model=model_key, dtype=dtype)
        state.age(chunk)
        rt = np.asarray(rt)
        ticks = c * chunk + np.arange(chunk)
        keep = (ticks >= WARMUP_TICKS) & (ticks < t_end_i)
        if keep.any():
            for i in range(b):
                r = rt[i][keep]
                rts[i].append(r[r > 0])
        cpu, mem = np.asarray(cpu), np.asarray(mem)
        if c % cpw == 0:
            win_hist, win_cpu, win_mem = hist, [cpu], [mem]
        else:
            win_hist = win_hist + hist
            win_cpu.append(cpu)
            win_mem.append(mem)
        if c % cpw == cpw - 1 or c == num_chunks - 1:
            cpu_w.append(np.mean(win_cpu, axis=0))
            mem_w.append(np.mean(win_mem, axis=0))
            det_carry, hot = detector_step(
                det_carry, win_hist, det=det, bins=bins, width=width,
                dtype=dtype)
            hot_w.append(np.asarray(hot))
    return {
        "rt": [np.concatenate(r) if r else np.zeros(0, np.float32)
               for r in rts],
        "cpu_util": np.stack(cpu_w, 1),
        "mem_util": np.stack(mem_w, 1),
        "hot": np.stack(hot_w, 1),
        "invalid": invalid,
        "window_ticks": cpw * chunk,
    }


def rt_stats(samples: np.ndarray) -> dict:
    """avg/p90/p99 of a pooled RT sample, as the drivers report them."""
    if samples.size == 0:
        return {"avg_rt": float("nan"), "p90_rt": float("nan"),
                "p99_rt": float("nan")}
    p90, p99 = np.percentile(samples, [90, 99])
    return {"avg_rt": float(samples.astype(np.float64).mean()),
            "p90_rt": float(p90), "p99_rt": float(p99)}


def replay_stats(config: dict, plan: dict, sim_seeds, *,
                 dtype=jnp.float32) -> dict:
    """The per-seed answers of a replay of ``plan``: avg/p90/p99 RT,
    utilization spread over the arrival phase, hot windows."""
    model = config["model"]
    chunk = model["chunk_ticks"]
    cpw = model["window_ticks"] // chunk
    t_end = int(round(plan["t_end"]))
    num_windows = -(-(t_end // chunk) // cpw)
    # RT is pooled through t_end even where that runs past the last whole
    # window (the replay simulates its padding); windows count to num_windows
    sim = simulate(config, plan["log"], t_end, sim_seeds,
                   num_chunks=max(num_windows * cpw, -(-t_end // chunk)),
                   dtype=dtype)
    for k in ("cpu_util", "mem_util", "hot"):
        sim[k] = sim[k][:, :num_windows]
    span = sim["window_ticks"]
    w_start = np.arange(num_windows) * span
    util = (w_start >= WARMUP_TICKS) & (
        w_start + span <= t_end - plan.get("settle_ticks", 40))
    if not util.any():
        util = np.ones(num_windows, bool)
    seeds = []
    for i, s in enumerate(sim_seeds):
        seeds.append({
            "sim_seed": int(s), **rt_stats(sim["rt"][i]),
            "cpu_util_std": float((100 * sim["cpu_util"][i][util])
                                  .std(axis=1).mean()),
            "mem_util_std": float((100 * sim["mem_util"][i][util])
                                  .std(axis=1).mean()),
            "hot_windows": int(sim["hot"][i].any(-1).sum()),
        })
    return {"seeds": seeds, "invalid": sim["invalid"]}
