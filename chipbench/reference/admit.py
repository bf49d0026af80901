"""Plain reference of the live loop's decisions: admission and control.

It imports nothing of the program.  From a configuration file, the run's
mutation log (``plan_out``), its simulation seed and what the run recorded
(the scheduler's decision log and the control loop's outcome records) it
recomputes three rules:

* **Admission** (Algorithm 1, ICO / ICO-F): a node is feasible when its
  Eq. 5-6 utilization with the pod's demand stays under both thresholds;
  its score is ``(1 - u_cpu)(1 - u_mem) - intf_nodes - forecast - intf_pod``
  from the recorded quantifier outputs and forecast term; the pod goes to
  the feasible node of highest score, the lowest index among equals, and
  is refused (-1) when none is feasible.  The Eq. 3 predictor's output
  (``intf_pod``) and the forecaster's term are taken as recorded data.
* **Loop outcomes**: every node the detector flagged in a window either
  had an action applied that the log holds, or a declining guard that
  holds: ``cooldown`` (an action on the node under ``cooldown`` windows
  before), ``interval`` (not an acting window), ``no_candidate`` (no
  offline pod on the node outside its per-pod cooldown, in the
  reference's own state), ``net_gain`` (the best candidate gained nothing),
  ``budget`` (the best candidate did not fit the budget left) or
  ``apply_failed`` (something was planned and nothing took).
* **Eq. 1 and Eqs. 5-6 terms**: each offer's ``intf_nodes`` (the weighted
  sum of per-slot average runqlat) and its utilization terms, from the
  reference's own replay of the telemetry window the offer's view covered.

The replay reuses ``sim.py``: its state, its random stream and its chunk
(RT samples, node histograms, utilization), its detector node track (the
flags of every window of the live run) and its RT statistics.  Only the
per-slot histograms and the CPU and memory demand an offer's terms need
are computed here, by a tick that draws the same stream.  ``dtype`` is as
in ``sim.py``: ``float32`` is the configuration's precision, ``bfloat16``
the control.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import sim

# a score within this of the best is a tie in float32: the program scores
# in float32 and every score term is O(1), so rounding moves a score by
# about 1e-7; a threshold within this of a node's utilization is a tie too
TIE = 1e-6

# the control loop's action kinds, as the mutation log's op on the node
# the action relieves
ACTION_OPS = {"evict_offline": "evict_off", "vertical_resize": "resize_off",
              "migrate_online": "migrate_on", "scale_out": "resize_on"}


def _terms_tick(st, prof, phys, model, t, key, dtype):
    """One tick's per-slot runqlat histograms (N, S, bins), CPU demand and
    memory used (N,), on the stream ``sim._tick`` draws."""
    f = lambda x: jnp.asarray(x).astype(dtype)   # noqa: E731
    k_qps, k_lat, _k_rt, _k_hw = jax.random.split(key, 4)
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
    cores = f(st["cores"])
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
    spt, bins = model["samples_per_tick"], model["histogram_bins"]
    tiny = jnp.finfo(jnp.float32).tiny

    def slot_hists(k, active):
        jit_ = f(1.0) + f(0.18) * f(jax.random.normal(
            jax.random.fold_in(k, 0), active.shape))
        mean = delay[:, None] * jnp.maximum(jit_, f(0.3))
        u = jax.random.uniform(jax.random.fold_in(k, 1),
                               (*active.shape, spt, 2), minval=tiny,
                               maxval=1.0)
        g = -jnp.log(f(u[..., 0]) * f(u[..., 1]))
        s = g * (mean[..., None] / f(2.0))
        idx = jnp.clip(jnp.floor(s.astype(jnp.float32) / model["bin_width"]),
                       0, bins - 1).astype(jnp.int32)
        idx = jnp.where(active[..., None], idx, -1)
        return (idx[..., None] == jnp.arange(bins)).sum(-2)

    hists = jnp.concatenate(
        [slot_hists(jax.random.fold_in(k_lat, 0), on_act),
         slot_hists(jax.random.fold_in(k_lat, 1), off_act)], axis=1)
    mem_used = mem_on.sum(-1) + mem_off.sum(-1) + f(2.0)
    return (hists.astype(jnp.float32), total_cpu.astype(jnp.float32),
            mem_used.astype(jnp.float32))


@partial(jax.jit, static_argnames=("model", "dtype"))
def _terms_chunk(st, prof, phys, t0, chunk_key, *, model, dtype):
    """A chunk's per-slot histogram sums and its mean CPU demand and
    memory used; offline slots drop out at the tick their countdown ends."""
    model = dict(model)
    ticks = model["chunk_ticks"]
    keys = jax.random.split(chunk_key, ticks)
    hists, cpus, mems = [], [], []
    for j in range(ticks):
        stj = dict(st)
        stj["off_active"] = st["off_active"] & (st["off_remaining"] > j)
        h, c, m = _terms_tick(stj, prof, phys, model, t0 + jnp.float32(j),
                              keys[j], dtype)
        hists.append(h)
        cpus.append(c)
        mems.append(m)
    return (sum(hists[1:], hists[0]), jnp.stack(cpus).mean(0),
            jnp.stack(mems).mean(0))


def _avg(hist: np.ndarray, width: float) -> np.ndarray:
    """Eq. 2: the histogram-weighted average runqlat (0 when empty)."""
    k = np.arange(hist.shape[-1], dtype=np.float32) * np.float32(width)
    den = hist.sum(-1)
    num = (hist * k).sum(-1)
    return np.where(den > 0, num / np.maximum(den, 1e-12), 0.0)


def _model_key(model: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in model.items()
                        if not isinstance(v, (list, dict))))


def replay(config: dict, plan: dict, sim_seed: int, windows, decisions, *,
           dtype=jnp.float32) -> dict:
    """Replay a live run's log at its simulation seed, chunk by chunk.

    ``windows``: the (start, end) ticks of the control loop's windows;
    ``decisions``: the scheduler's decision log (each offer's pod demand
    and the window its view covered).  Returns the pooled online RT
    samples over [30, t_end), each window's mean CPU/memory utilization
    (W, N) and detector flags (W, N), the offline slots active at each
    window's end (W, N, S_OFF), each offer's Eqs. 5-6 ``utiliz_cpu``/
    ``utiliz_mem`` and Eq. 1 ``intf_nodes`` (N,), and ``invalid`` (log
    entries the state refuses).
    """
    offers = [(d["t"] - d["window_ticks"], d["t"]) for d in decisions]
    model = config["model"]
    chunk = model["chunk_ticks"]
    t_end = int(round(plan["t_end"]))
    num_chunks = -(-t_end // chunk)
    consts = sim._constants(config)
    prof = {k: jnp.asarray(v) for k, v in consts["prof"].items()}
    phys = consts["phys"]
    mkey = _model_key(model)
    keys = sim.chunk_keys(jax.random.PRNGKey(int(sim_seed))[None],
                          num_chunks)[0]
    by_chunk: dict[int, list] = {}
    for e in plan["log"]:
        if e[1] < t_end:
            by_chunk.setdefault(int(e[1]) // chunk, []).append(e)
    win_end = {int(b) // chunk: i for i, (_, b) in enumerate(windows)}
    need = set()
    for a, b in offers:
        need.update(range(int(a) // chunk, int(b) // chunk))
    bins, width = model["histogram_bins"], model["bin_width"]
    n = config["nodes"]
    det = tuple(sorted(config["detector"].items()))
    carry = (jnp.zeros((1, n, bins), dtype), jnp.zeros((1, n), dtype),
             jnp.zeros((1, n), dtype), jnp.int32(0))

    state = sim.State(config)
    invalid = 0
    rts = []
    hist_c, cpu_c, mem_c, terms = {}, {}, {}, {}
    hot = np.zeros((len(windows), n), bool)
    off_at_end = np.zeros((len(windows), n, config["offline_slots"]), bool)
    w_cpu = np.zeros((len(windows), n))
    w_mem = np.zeros((len(windows), n))
    for c in range(num_chunks + 1):
        if c in win_end:
            # the loop steps at the window's end, before the entries
            # logged at that tick (its own actions, then the offers)
            i = win_end[c]
            off_at_end[i] = state.off_active
            cs = range(int(windows[i][0]) // chunk, c)
            node_hist = sum(hist_c[k] for k in cs)
            carry, h = sim.detector_step(carry, jnp.asarray(node_hist)[None],
                                         det=det, bins=bins, width=width,
                                         dtype=dtype)
            hot[i] = np.asarray(h)[0]
            w_cpu[i] = np.mean([cpu_c[k] for k in cs], axis=0)
            w_mem[i] = np.mean([mem_c[k] for k in cs], axis=0)
        if c == num_chunks:
            break
        for e in by_chunk.get(c, ()):
            try:
                state.apply(e)
            except sim.InvalidEvent:
                invalid += 1
        dev = {k: jnp.asarray(v) for k, v in state.arrays().items()}
        rt, hist, cpu, mem = sim._chunk(dev, prof, phys,
                                        jnp.float32(c * chunk), keys[None, c],
                                        model=mkey, dtype=dtype)
        if c in need:
            terms[c] = [np.asarray(x) for x in _terms_chunk(
                dev, prof, phys, jnp.float32(c * chunk), keys[c],
                model=mkey, dtype=dtype)]
        state.age(chunk)
        rt = np.asarray(rt)[0]
        ticks = c * chunk + np.arange(chunk)
        keep = (ticks >= sim.WARMUP_TICKS) & (ticks < t_end)
        r = rt[keep]
        rts.append(r[r > 0])
        hist_c[c] = np.asarray(hist)[0]
        cpu_c[c], mem_c[c] = np.asarray(cpu)[0], np.asarray(mem)[0]

    adm = config["admission"]
    w, cfg = adm["weights"], adm["scheduler_config"]
    mc = config["machine_class"]
    s_on = config["online_slots"]
    offer_terms = []
    for d, (a, b) in zip(decisions, offers):
        cs = range(int(a) // chunk, int(b) // chunk)
        avg = _avg(sum(terms[k][0] for k in cs), width)
        cpu_cur = np.mean([terms[k][1] for k in cs], axis=0)
        mem_cur = np.mean([terms[k][2] for k in cs], axis=0)
        offer_terms.append({
            "utiliz_cpu": (cpu_cur.astype(np.float64)
                           + cfg["w_d"] * d["cpu_demand"]) / mc["cores"],
            "utiliz_mem": (mem_cur.astype(np.float64)
                           + cfg["w_e"] * d["mem_demand"]) / mc["mem_gb"],
            "intf_nodes": ((w["w_a"] * avg[:, :s_on].sum(-1)
                            + w["w_b"] * avg[:, s_on:].sum(-1))
                           / (width * (bins - 1)))})
    return {"rt": np.concatenate(rts) if rts else np.zeros(0, np.float32),
            "cpu_util": w_cpu, "mem_util": w_mem, "hot": hot,
            "off_active": off_at_end, "offers": offer_terms,
            "invalid": invalid}


def choice_ok(config: dict, entry: dict) -> bool:
    """Algorithm 1 on the recorded terms: does the recorded choice hold?"""
    cfg = config["admission"]["scheduler_config"]
    u_cpu = np.asarray(entry["utiliz_cpu"], np.float64)
    u_mem = np.asarray(entry["utiliz_mem"], np.float64)
    thr_c, thr_m = cfg["cpu_threshold"], cfg["mem_threshold"]
    strict = (u_cpu <= thr_c - TIE) & (u_mem <= thr_m - TIE)
    loose = (u_cpu <= thr_c + TIE) & (u_mem <= thr_m + TIE)
    intf_h = np.asarray(entry["intf_nodes"], np.float64)
    if entry["forecast_term"] is not None:
        intf_h = intf_h + np.asarray(entry["forecast_term"], np.float64)
    score = ((1.0 - u_cpu) * (1.0 - u_mem) - intf_h
             - np.asarray(entry["intf_pod"], np.float64))
    chosen = entry["chosen"]
    if chosen < 0:
        return not strict.any()
    if not loose[chosen]:
        return False
    s = score[chosen]
    better = strict & (score > s + TIE)
    first = strict & (score == s) & (np.arange(score.size) < chosen)
    return not (better.any() or first.any())


def outcome_faults(config: dict, outcomes: list, log: list,
                   off_active: np.ndarray, cooled) -> int:
    """Flagged (node, window) pairs without an applied action the log
    holds or a declining guard that holds.

    ``off_active``: (W, N, S_OFF) offline slots active at each window's
    end in the reference's state; ``cooled``: (W, N, S_OFF) offline slots
    whose tenant the loop acted on within its per-pod cooldown.
    """
    ctl = config["control"]
    logged = {(e[0], float(e[1]), int(e[2])) for e in log}
    last: dict[int, int] = {}     # node -> step of its last reactive action
    faults = 0
    for i, rec in enumerate(outcomes):
        step = rec["step"]
        flagged = {f["node"] for f in rec["flagged"]}
        faults += len((set(rec["hot"]) | set(rec["proactive"])) ^ flagged)
        for f in rec["flagged"]:
            node = f["node"]
            applied = [a for a in f["actions"] if a["applied"]]
            guard = f.get("guard")
            if applied:
                ok = all((ACTION_OPS.get(a["kind"]), rec["t"], node) in logged
                         for a in applied)
            elif guard == "cooldown":
                ok = (node in last and f.get("last_acted") == last[node]
                      and step - last[node] < ctl["loop"]["cooldown"])
            elif guard == "interval":
                ok = step % ctl["loop"]["interval"] != 0
            elif guard == "apply_failed":
                ok = bool(f["actions"])
            elif guard == "no_candidate":
                ok = not (off_active[i, node] & ~cooled[i, node]).any()
            elif guard == "net_gain":
                ok = f.get("best_net_gain", 1.0) <= 0.0
            elif guard == "budget":
                ok = (f.get("budget") == ctl["policy"]["budget"]
                      and f["spent"] <= rec["spent"] + TIE
                      and f["spent"] + f["cost"] > f["budget"])
            else:
                ok = False
            faults += not ok
        for f in rec["flagged"]:
            if any(a["applied"] and not a["proactive"] for a in f["actions"]):
                last[f["node"]] = step
    return faults


def cooled_slots(config: dict, outcomes: list, log: list) -> np.ndarray:
    """(W, N, S_OFF): offline slots whose tenant the loop throttled (the
    one offline action that leaves the pod in place) within the per-pod
    cooldown before each window, and that no new job has taken since."""
    n, s_off = config["nodes"], config["offline_slots"]
    uid_cd = config["control"]["loop"]["uid_cooldown"]
    out = np.zeros((len(outcomes), n, s_off), bool)
    steps = [rec["step"] for rec in outcomes]
    at = {rec["t"]: rec["step"] for rec in outcomes}
    marks: dict[tuple, int] = {}     # (node, slot) -> step throttled
    events = sorted(log, key=lambda e: e[1])
    j = 0
    for i, rec in enumerate(outcomes):
        # entries logged before this window's step: throttles by earlier
        # steps, and new jobs placed into a throttled slot
        while j < len(events) and events[j][1] < rec["t"]:
            e = events[j]
            if e[0] == "resize_off" and e[1] in at:
                marks[(int(e[2]), int(e[3]))] = at[e[1]]
            elif e[0] == "place_off":
                marks.pop((int(e[2]), int(e[3])), None)
            j += 1
        for (node, slot), s in marks.items():
            if steps[i] - s < uid_cd:
                out[i, node, slot] = True
    return out
