"""Pure, immutable cluster-state pytree and the scanned/batched rollout core.

``Cluster`` (``repro.cluster.simulator``) used to own its arrays as a raw
dict and advance time chunk-by-chunk through Python — every 3-day trace
paid minutes of interpreter time dispatching 10-tick jit calls, which is
why benches ran 2 seeds behind a 90-minute CI timeout.  This module is the
array-first rebuild:

* ``ClusterState`` — a frozen ``register_dataclass`` pytree holding the 12
  per-node/per-slot arrays.  It is a valid jit/scan/vmap carry, and the
  ``Cluster`` shell now stores exactly one of these (with a dict-style
  ``__getitem__``/``items`` shim so existing readers keep working).

* ``FleetParams`` — per-node delay-curve parameters (base, scale, knee,
  oversubscription slope) as a read-only pytree that rides alongside
  ``profiles`` through every rollout entry point.  ``cluster.fleet``
  builds heterogeneous instances from machine-class tables;
  ``FleetParams.uniform`` is the homogeneous degenerate case and
  reproduces the pre-fleet constants bit-for-bit.

* Pure transforms — ``place_online`` / ``place_offline`` / ``evict_*`` /
  ``migrate_*`` / ``resize_*`` / ``reconcile`` are masked ``.at[...]``
  updates keyed on explicit (node, slot) indices: no Python dict state, so
  the same functions serve the host-side shell and the traced replay path.

* Event replay — the shell logs every mutation as a small host tuple;
  ``extract_plan`` buckets the log into padded per-chunk event arrays and
  ``apply_events`` replays them inside the scan with one ``lax.switch``
  over op codes, so an entire experiment's placement/mitigation schedule
  becomes data a jit'd rollout can consume.

* Scanned rollout — ``rollout_chunks`` scans a whole multi-chunk window in
  one dispatch (the live ``Cluster.rollout``: each chunk keyed from
  ``chunk_key_stream``, summaries merged host-side by
  ``merge_summaries``), and ``_scan_windows_impl`` scans telemetry
  *windows* with the detector's node-track CUSUM and the forecaster's
  harmonic moments folded into the carry.  Both run the one tick body,
  ``_tick``, through ``_window_core``.

* ``batched_rollout`` — vmap of ``_scan_windows_impl`` over a leading seed
  axis: one call evaluates 20+ simulation seeds of a 3-day trace against a
  fixed placement/action plan (common-random-placements replay).  With
  ``devices=N`` the seed axis is additionally **sharded across host
  devices** via ``shard_map`` (the ``launch/mesh.py`` +
  ``XLA_FLAGS=--xla_force_host_platform_device_count`` idiom from the
  model layer): the batch is padded to a device multiple, each device runs
  the identical vmapped scan over its shard, and the padding is sliced off
  host-side — per-seed results are bitwise-identical to the single-device
  vmap path because seeds never communicate.

Compile-once engine properties:

* The ``ClusterState`` scan carry is **donated** at the ``rollout_chunks``
  and stacked ``batched_rollout`` entry points (``donate_argnums``), so
  XLA reuses the input buffers for the output state instead of holding
  both live across the dispatch — at 5k nodes that halves the peak
  footprint of the mutable state.  Callers must treat the passed-in
  state as consumed (the ``Cluster`` shell always reassigns
  ``self.state`` from the result).
* ``extract_plan(..., bucket=True)`` pads the event arrays to power-of-two
  **size-class buckets** (events-per-chunk and window count), so every
  same-class plan of a scenario suite or optimizer candidate sweep replays
  through ONE compiled executable instead of recompiling per plan.  NOOP
  padding events are identity transforms, and ``batched_rollout`` takes the
  plan's real window count as traced data and skips the padded windows, so
  the un-padded prefix is bitwise unchanged and the padding costs no ticks.

The per-window outputs are deliberately "lite" (RT series, window-mean
utilization, folded hotspot flags) — stacking per-tick slot histograms
across a 3-day x 20-seed batch would cost ~GBs; node-level histograms are
accumulated in the carry instead, which is all the detector track needs.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metric

S_ON = 8    # online slots per node
S_OFF = 6   # offline slots per node
SAMPLES_PER_TICK = 16
TICKS_PER_DAY = 2880.0

# contention model constants (the homogeneous defaults; per-node values
# live in FleetParams and reduce to these on a single-class fleet)
OS_BASE_CORES = 0.5
RUNQLAT_BASE = 3.0          # latency units under no contention
RUNQLAT_SCALE = 55.0        # scale of the delay curve
RHO_EPS = 0.05              # knee clamp: caps the 1/(1-rho) blow-up
OVERSUB_SLOPE = 0.15        # thread-oversubscription contention slope
GAMMA_SHAPE = 2.0           # runqlat draws are Erlang(2): -log(U1*U2)

CHUNK = 10  # fixed inner scan length -> one small shared XLA compilation


def _season(t, phase):
    return 1.0 + 0.35 * jnp.sin(2 * jnp.pi * t / TICKS_PER_DAY + phase) \
               + 0.12 * jnp.sin(4 * jnp.pi * t / TICKS_PER_DAY + 1.7 * phase)


def delay_curve(rho, xp=jnp, base=RUNQLAT_BASE, scale=RUNQLAT_SCALE,
                knee=RHO_EPS):
    """M/G/1-PS style delay vs run-queue pressure: convex, explodes near 1.

    The single source of truth for the contention curve — the rollout
    kernel applies it per tick (xp=jnp, under jit, with per-node
    ``FleetParams`` arrays for base/scale/knee) and the mitigation policy
    reuses it host-side (xp=np, per-node float64 parameters from the
    view), so retuning the curve retunes both.  The defaults are the
    homogeneous machine class; uniform per-node arrays filled with them
    are elementwise-identical to the scalars, which is what makes the
    single-class fleet the bitwise degenerate case.
    """
    return base + scale * rho**2 / xp.maximum(1.0 - rho, knee)


# --------------------------------------------------------------------------
# the pytree
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ClusterState:
    """Immutable per-node/per-slot cluster arrays, registered as a pytree.

    Online slots carry (type, mean QPS, diurnal phase); offline slots carry
    (cores, threads, mem, burstiness, remaining ticks).  ``*_active`` masks
    gate every term in the tick kernel, so stale parameters in inactive
    slots are harmless — ``reconcile`` clears them for host-side readers.
    """

    on_active: jax.Array      # (N, S_ON) bool
    on_type: jax.Array        # (N, S_ON) int32
    on_qps_mean: jax.Array    # (N, S_ON) float32
    on_phase: jax.Array       # (N, S_ON) float32
    off_active: jax.Array     # (N, S_OFF) bool
    off_cores: jax.Array      # (N, S_OFF) float32
    off_threads: jax.Array    # (N, S_OFF) float32
    off_mem: jax.Array        # (N, S_OFF) float32
    off_burst: jax.Array      # (N, S_OFF) float32
    off_remaining: jax.Array  # (N, S_OFF) int32
    cpu_sum: jax.Array        # (N,) float32
    mem_sum: jax.Array        # (N,) float32

    @classmethod
    def create(cls, num_nodes: int, cores=32.0,
               mem_gb=64.0) -> "ClusterState":
        """``cores``/``mem_gb`` are scalars (homogeneous fleet) or (N,)
        per-node capacity arrays (``jnp.full`` broadcasts either)."""
        return cls(
            on_active=jnp.zeros((num_nodes, S_ON), bool),
            on_type=jnp.zeros((num_nodes, S_ON), jnp.int32),
            on_qps_mean=jnp.zeros((num_nodes, S_ON), jnp.float32),
            on_phase=jnp.zeros((num_nodes, S_ON), jnp.float32),
            off_active=jnp.zeros((num_nodes, S_OFF), bool),
            off_cores=jnp.zeros((num_nodes, S_OFF), jnp.float32),
            off_threads=jnp.zeros((num_nodes, S_OFF), jnp.float32),
            off_mem=jnp.zeros((num_nodes, S_OFF), jnp.float32),
            off_burst=jnp.ones((num_nodes, S_OFF), jnp.float32),
            off_remaining=jnp.zeros((num_nodes, S_OFF), jnp.int32),
            cpu_sum=jnp.full((num_nodes,), cores, jnp.float32),
            mem_sum=jnp.full((num_nodes,), mem_gb, jnp.float32),
        )

    @property
    def num_nodes(self) -> int:
        return self.cpu_sum.shape[-1]

    def replace(self, **kw) -> "ClusterState":
        return dataclasses.replace(self, **kw)

    # dict-style compat: Cluster.state was a plain dict of arrays before the
    # pytree refactor, and the control plane / tests read it by key
    def __getitem__(self, name: str):
        return getattr(self, name)

    def keys(self):
        return [f.name for f in dataclasses.fields(self)]

    def items(self):
        return [(f.name, getattr(self, f.name))
                for f in dataclasses.fields(self)]


# Every field is a traced array leaf; repro-lint R2 checks this literal
# split stays in sync with the class, so adding a field without deciding
# its data/meta side fails CI instead of failing inside a jit.
jax.tree_util.register_dataclass(
    ClusterState,
    data_fields=[
        "on_active", "on_type", "on_qps_mean", "on_phase",
        "off_active", "off_cores", "off_threads", "off_mem",
        "off_burst", "off_remaining", "cpu_sum", "mem_sum",
    ],
    meta_fields=[],
)


@dataclasses.dataclass(frozen=True)
class FleetParams:
    """Per-node delay-curve parameters, carried through the rollout as
    arrays rather than Python constants.

    A separate pytree from ``ClusterState`` on purpose: the state carries
    what the simulation *mutates* (placements, offline countdowns), while
    the fleet carries what the hardware *is* — machine-class physics that
    no transform ever writes.  Keeping them apart means the event-replay
    and scan carries stay exactly as wide as the mutable state, and the
    fleet rides alongside ``profiles`` as a second read-only input.

    ``FleetParams.uniform(n)`` fills every array with the module
    constants; uniform float32 arrays broadcast elementwise exactly like
    the scalar literals they replace, so a homogeneous fleet reproduces
    the pre-fleet kernel bit-for-bit.
    """

    delay_base: jax.Array     # (N,) float32 — RUNQLAT_BASE per node
    delay_scale: jax.Array    # (N,) float32 — RUNQLAT_SCALE per node
    rho_knee: jax.Array       # (N,) float32 — RHO_EPS per node
    oversub_slope: jax.Array  # (N,) float32 — OVERSUB_SLOPE per node

    @classmethod
    def uniform(cls, num_nodes: int) -> "FleetParams":
        return cls(
            delay_base=jnp.full((num_nodes,), RUNQLAT_BASE, jnp.float32),
            delay_scale=jnp.full((num_nodes,), RUNQLAT_SCALE, jnp.float32),
            rho_knee=jnp.full((num_nodes,), RHO_EPS, jnp.float32),
            oversub_slope=jnp.full((num_nodes,), OVERSUB_SLOPE, jnp.float32),
        )

    @property
    def num_nodes(self) -> int:
        return self.delay_base.shape[-1]


jax.tree_util.register_dataclass(
    FleetParams,
    data_fields=[
        "delay_base", "delay_scale", "rho_knee", "oversub_slope",
    ],
    meta_fields=[],
)


# --------------------------------------------------------------------------
# pure transforms (masked updates keyed on explicit slot indices)
# --------------------------------------------------------------------------


def place_online(state: ClusterState, node, slot, type_id, qps,
                 phase) -> ClusterState:
    idx = (node, slot)
    return state.replace(
        on_active=state.on_active.at[idx].set(True),
        on_type=state.on_type.at[idx].set(jnp.asarray(type_id, jnp.int32)),
        on_qps_mean=state.on_qps_mean.at[idx].set(qps),
        on_phase=state.on_phase.at[idx].set(phase),
    )


def place_offline(state: ClusterState, node, slot, cores, threads, mem,
                  burst, remaining) -> ClusterState:
    idx = (node, slot)
    return state.replace(
        off_active=state.off_active.at[idx].set(True),
        off_cores=state.off_cores.at[idx].set(cores),
        off_threads=state.off_threads.at[idx].set(threads),
        off_mem=state.off_mem.at[idx].set(mem),
        off_burst=state.off_burst.at[idx].set(burst),
        off_remaining=state.off_remaining.at[idx].set(
            jnp.asarray(remaining, jnp.int32)),
    )


def evict_online(state: ClusterState, node, slot) -> ClusterState:
    # clears the slot params too: the kernel masks by on_active either
    # way, but host-side readers (nodes_data, pressure scans) between a
    # remove and the next reconcile must not see ghost allocations
    idx = (node, slot)
    return state.replace(
        on_active=state.on_active.at[idx].set(False),
        on_type=state.on_type.at[idx].set(0),
        on_qps_mean=state.on_qps_mean.at[idx].set(0.0),
        on_phase=state.on_phase.at[idx].set(0.0),
    )


def evict_offline(state: ClusterState, node, slot) -> ClusterState:
    idx = (node, slot)
    return state.replace(
        off_active=state.off_active.at[idx].set(False),
        off_cores=state.off_cores.at[idx].set(0.0),
        off_threads=state.off_threads.at[idx].set(0.0),
        off_mem=state.off_mem.at[idx].set(0.0),
        off_burst=state.off_burst.at[idx].set(1.0),
        off_remaining=state.off_remaining.at[idx].set(0),
    )


def migrate_online(state: ClusterState, src, src_slot, dst,
                   dst_slot) -> ClusterState:
    si, di = (src, src_slot), (dst, dst_slot)

    def move(a, fill):
        return a.at[di].set(a[si]).at[si].set(fill)

    return state.replace(
        on_active=state.on_active.at[di].set(True).at[si].set(False),
        on_type=move(state.on_type, 0),
        on_qps_mean=move(state.on_qps_mean, 0.0),
        on_phase=move(state.on_phase, 0.0),
    )


def migrate_offline(state: ClusterState, src, src_slot, dst,
                    dst_slot) -> ClusterState:
    si, di = (src, src_slot), (dst, dst_slot)

    def move(a, fill):
        return a.at[di].set(a[si]).at[si].set(fill)

    return state.replace(
        off_active=state.off_active.at[di].set(True).at[si].set(False),
        off_cores=move(state.off_cores, 0.0),
        off_threads=move(state.off_threads, 0.0),
        off_mem=move(state.off_mem, 0.0),
        off_burst=move(state.off_burst, 1.0),
        off_remaining=move(state.off_remaining, 0),
    )


def resize_online(state: ClusterState, node, slot, qps) -> ClusterState:
    return state.replace(
        on_qps_mean=state.on_qps_mean.at[node, slot].set(qps))


def resize_offline(state: ClusterState, node, slot, cores, threads, mem,
                   remaining) -> ClusterState:
    """Set an offline slot's post-resize values (the shell computes the
    work-conserving rescale host-side and logs absolute targets)."""
    idx = (node, slot)
    return state.replace(
        off_cores=state.off_cores.at[idx].set(cores),
        off_threads=state.off_threads.at[idx].set(threads),
        off_mem=state.off_mem.at[idx].set(mem),
        off_remaining=state.off_remaining.at[idx].set(
            jnp.asarray(remaining, jnp.int32)),
    )


def reconcile(state: ClusterState):
    """Clear finished offline slots (deactivated by the kernel but still
    carrying parameters).  Returns (new_state, stale_mask)."""
    stale = (~state.off_active) & (state.off_cores > 0.0)

    def clr(a, fill):
        return jnp.where(stale, fill, a)

    cleared = state.replace(
        off_cores=clr(state.off_cores, 0.0),
        off_threads=clr(state.off_threads, 0.0),
        off_mem=clr(state.off_mem, 0.0),
        off_burst=clr(state.off_burst, 1.0),
        off_remaining=clr(state.off_remaining, 0),
    )
    return cleared, stale


# --------------------------------------------------------------------------
# event replay: op-coded mutations applied inside the scan
# --------------------------------------------------------------------------

EV_PLACE_ON, EV_PLACE_OFF, EV_EVICT_ON, EV_EVICT_OFF, EV_MIGRATE_ON, \
    EV_MIGRATE_OFF, EV_RESIZE_ON, EV_RESIZE_OFF, EV_NOOP = range(9)

_OP_CODES = {
    "place_on": EV_PLACE_ON,
    "place_off": EV_PLACE_OFF,
    "evict_on": EV_EVICT_ON,
    "evict_off": EV_EVICT_OFF,
    "migrate_on": EV_MIGRATE_ON,
    "migrate_off": EV_MIGRATE_OFF,
    "resize_on": EV_RESIZE_ON,
    "resize_off": EV_RESIZE_OFF,
}


def _apply_event(state: ClusterState, ev) -> ClusterState:
    n, s, d, ds = ev["node"], ev["slot"], ev["dst"], ev["dslot"]
    f = ev["f"]
    branches = [
        lambda st: place_online(st, n, s, f[0].astype(jnp.int32), f[1], f[2]),
        lambda st: place_offline(st, n, s, f[0], f[1], f[2], f[3],
                                 f[4].astype(jnp.int32)),
        lambda st: evict_online(st, n, s),
        lambda st: evict_offline(st, n, s),
        lambda st: migrate_online(st, n, s, d, ds),
        lambda st: migrate_offline(st, n, s, d, ds),
        lambda st: resize_online(st, n, s, f[0]),
        lambda st: resize_offline(st, n, s, f[0], f[1], f[2],
                                  f[4].astype(jnp.int32)),
        lambda st: st,  # EV_NOOP padding
    ]
    return jax.lax.switch(ev["op"], branches, state)


def apply_events(state: ClusterState, events: dict) -> ClusterState:
    """Apply one chunk's padded event list (leaves shaped (E, ...)) in order."""

    def body(st, ev):
        return _apply_event(st, ev), None

    state, _ = jax.lax.scan(body, state, events)
    return state


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


def extract_plan(log, t0: float, num_windows: int,
                 chunks_per_window: int, bucket: bool = False) -> dict:
    """Bucket a Cluster mutation log into padded per-chunk event arrays.

    ``log`` entries are the host tuples the shell records:
    ``(op, t, node, slot, *params)`` (or ``(op, t, src, ss, dst, ds)`` for
    migrations).  An event logged at time ``t`` is applied before the chunk
    covering ``t`` — mutations always happen at chunk boundaries (the shell
    only mutates between rollouts), so this reproduces the shell ordering
    exactly.  Returns ``{"op", "node", "slot", "dst", "dslot", "f"}`` with
    leading shape (num_windows, chunks_per_window, E_max).

    ``bucket=True`` rounds the two plan-dependent dimensions — events per
    chunk and the window count — up to the next power of two, padding with
    NOOP events / event-free windows.  Every plan in a size class then
    shares one traced shape, so an entire scenario suite or optimizer
    candidate sweep replays through a single compiled executable.  The
    padding is semantically inert: NOOPs are identity transforms, and a
    padded window lies past the plan's real span, where
    ``batched_rollout(..., num_windows=<real count>)`` runs no tick of it
    (the per-seed chunk-key stream is prefix-stable), so the un-padded
    prefix of the replay is bitwise unchanged.
    """
    buckets: list[list] = [[] for _ in range(num_windows * chunks_per_window)]
    for entry in log:
        c = int((entry[1] - t0) // CHUNK)
        if c < 0 or c >= len(buckets):
            raise ValueError(
                f"log entry at t={entry[1]} outside the planned span "
                f"[{t0}, {t0 + len(buckets) * CHUNK})")
        buckets[c].append(entry)
    emax = max(1, max((len(b) for b in buckets), default=1))
    if bucket:
        emax = _next_pow2(emax)
        num_windows = _next_pow2(num_windows)
    shape = (num_windows, chunks_per_window, emax)
    plan = {
        "op": np.full(shape, EV_NOOP, np.int32),
        "node": np.zeros(shape, np.int32),
        "slot": np.zeros(shape, np.int32),
        "dst": np.zeros(shape, np.int32),
        "dslot": np.zeros(shape, np.int32),
        "f": np.zeros(shape + (5,), np.float32),
    }
    for c, evs in enumerate(buckets):
        w, cw = divmod(c, chunks_per_window)
        for e, entry in enumerate(evs):
            kind = entry[0]
            plan["op"][w, cw, e] = _OP_CODES[kind]
            plan["node"][w, cw, e] = entry[2]
            plan["slot"][w, cw, e] = entry[3]
            if kind in ("migrate_on", "migrate_off"):
                plan["dst"][w, cw, e] = entry[4]
                plan["dslot"][w, cw, e] = entry[5]
            else:
                vals = entry[4:]
                plan["f"][w, cw, e, :len(vals)] = vals
    return plan


# --------------------------------------------------------------------------
# the tick kernel (moved verbatim from simulator._rollout, dict -> pytree)
# --------------------------------------------------------------------------


def _tick(st: ClusterState, profiles, fleet: FleetParams, t, key):
    k_qps, k_lat, k_rt, k_hw = jax.random.split(key, 4)

    on_active = st.on_active          # (N, S_ON) bool
    on_type = st.on_type              # (N, S_ON) int32
    on_qps_mean = st.on_qps_mean      # (N, S_ON)
    on_phase = st.on_phase

    qps_noise = 1.0 + 0.06 * jax.random.normal(k_qps, on_qps_mean.shape)
    qps_t = on_qps_mean * _season(t, on_phase) * qps_noise
    qps_t = jnp.where(on_active, jnp.maximum(qps_t, 0.0), 0.0)

    cpu_on = jnp.where(
        on_active,
        profiles["cpu_per_qps"][on_type] * qps_t + profiles["cpu_base"][on_type],
        0.0,
    )
    thr_on = jnp.where(on_active, profiles["threads_per_qps"][on_type] * qps_t, 0.0)
    mem_on = jnp.where(
        on_active,
        profiles["mem_per_qps"][on_type] * qps_t + profiles["mem_base"][on_type],
        0.0,
    )

    off_active = st.off_active        # (N, S_OFF)
    cpu_off = jnp.where(off_active, st.off_cores, 0.0)
    thr_off = jnp.where(off_active, st.off_threads, 0.0)
    mem_off = jnp.where(off_active, st.off_mem, 0.0)
    burst_off = jnp.where(off_active, st.off_burst, 0.0)

    cores = st.cpu_sum                # (N,)
    # measured CPU demand uses *average* usage; run-queue pressure uses
    # *peak* (bursty) usage -- this information loss is exactly why
    # utilization under-predicts interference (paper Section II).
    total_cpu = cpu_on.sum(-1) + cpu_off.sum(-1) + OS_BASE_CORES
    pressure_cpu = cpu_on.sum(-1) + (cpu_off * burst_off).sum(-1) + OS_BASE_CORES
    rho = total_cpu / cores
    rho_p = pressure_cpu / cores
    threads_total = thr_on.sum(-1) + thr_off.sum(-1) + 2.0

    # M/G/1-PS style delay curve: convex in rho, explodes near 1.0 —
    # per-node (N,) parameters broadcast against the (N,) pressure
    delay = delay_curve(rho_p, base=fleet.delay_base,
                        scale=fleet.delay_scale, knee=fleet.rho_knee)
    # thread-count pressure adds a second contention path
    delay = delay * (1.0 + fleet.oversub_slope
                     * jnp.maximum(threads_total / cores - 1.0, 0.0))
    # tick-level lognormal jitter (scheduling is noisy)
    delay = delay * jnp.exp(
        0.13 * jax.random.normal(jax.random.fold_in(k_lat, 99), delay.shape)
    )
    delay = jnp.clip(delay, 0.0, 2.5 * metric.OVERFLOW_EDGE)

    # per-pod runqlat samples (gamma, mean == node delay x pod jitter)
    def pod_samples(key, active, n_slots):
        jit_ = 1.0 + 0.18 * jax.random.normal(
            jax.random.fold_in(key, 0), active.shape
        )
        mean = delay[:, None] * jnp.maximum(jit_, 0.3)
        # Gamma(shape=2) is Erlang(2): the sum of two unit exponentials,
        # sampled exactly as -log(U1*U2) -- two uniforms and a log instead
        # of jax.random.gamma's rejection loop
        u = jax.random.uniform(
            jax.random.fold_in(key, 1), (*active.shape, SAMPLES_PER_TICK, 2),
            minval=jnp.finfo(jnp.float32).tiny, maxval=1.0,
        )
        g = -jnp.log(u[..., 0] * u[..., 1])
        samples = g * (mean[..., None] / GAMMA_SHAPE)
        w = jnp.broadcast_to(active[..., None], samples.shape).astype(jnp.float32)
        return samples, w, mean

    s_on, w_on, mean_on = pod_samples(jax.random.fold_in(k_lat, 0), on_active, S_ON)
    s_off, w_off, _ = pod_samples(jax.random.fold_in(k_lat, 1), off_active, S_OFF)
    hist_on = metric.histogram(s_on, w_on)     # (N, S_ON, 200)
    hist_off = metric.histogram(s_off, w_off)  # (N, S_OFF, 200)

    # node-level measured telemetry
    cpu_util = jnp.minimum(total_cpu, cores) / cores
    mem_used = mem_on.sum(-1) + mem_off.sum(-1) + 2.0
    mem_util = jnp.minimum(mem_used, st.mem_sum) / st.mem_sum
    n_pods = on_active.sum(-1) + off_active.sum(-1)

    # online response time: service term + queueing-delay term + a
    # cache-contention term the runqlat metric does not capture
    base_rt = profiles["base_rt"][on_type]
    sat = jnp.maximum(qps_t / profiles["qps_cap"][on_type] - 0.8, 0.0)
    cache_term = 0.06 * base_rt * jnp.minimum(mem_used / st.mem_sum, 1.2)[:, None]
    rt = base_rt * (1.0 + 1.5 * sat) \
        + profiles["rt_per_runqlat"][on_type] * mean_on \
        + cache_term \
        + 0.06 * base_rt * jax.random.normal(k_rt, on_active.shape)
    rt = jnp.where(on_active, jnp.maximum(rt, 0.5), 0.0)

    # hardware events (per Table III), load-dependent with noise
    hw_noise = 1.0 + 0.05 * jax.random.normal(k_hw, (cores.shape[0], 8))
    used = jnp.minimum(total_cpu, cores)
    instructions = used * 2.4e9
    cache_pressure = jnp.minimum(mem_used / st.mem_sum, 1.2) + 0.04 * n_pods
    ipc = jnp.maximum(2.2 - 0.7 * jnp.minimum(rho, 1.3) - 0.3 * cache_pressure, 0.4)
    cycles = instructions / ipc
    cache_refs = instructions * 0.30
    cache_misses = cache_refs * (0.02 + 0.08 * cache_pressure)
    branch_ins = instructions * 0.18
    branch_miss = branch_ins * (0.01 + 0.02 * jnp.minimum(rho, 1.5))
    ctx_sw = threads_total * 120.0 * (1.0 + jnp.maximum(rho - 0.7, 0.0) * 3.0)
    migrations = ctx_sw * 0.02
    hw = jnp.stack(
        [cycles, instructions, cache_refs, cache_misses,
         branch_ins, branch_miss, ctx_sw, migrations], axis=-1
    ) * hw_noise

    # perf metrics (12 cols, Table III order)
    qps_node = qps_t.sum(-1)
    perf = jnp.stack(
        [
            cpu_util,
            mem_util,
            0.25 * mem_used,                     # mem_cache
            1500.0 * total_cpu,                  # mem_pgfault
            3.0 * mem_off.sum(-1),               # mem_pgmajfault
            0.8 * mem_used,                      # working_set
            0.7 * mem_used,                      # memory_rss
            0.002 * qps_node,                    # net_recv_avg (MB/s)
            1.2 * qps_node,                      # net_recv_packets_avg
            0.008 * qps_node,                    # net_send_avg
            1.1 * qps_node,                      # net_send_packets_avg
            0.5 * cpu_off.sum(-1),               # disk_io_avg
        ],
        axis=-1,
    )

    out = {
        "hist_on": hist_on,
        "hist_off": hist_off,
        "rt": rt,
        "qps": qps_t,
        "cpu_util": cpu_util,
        "mem_util": mem_util,
        "mem_used": mem_used,
        "cpu_demand": total_cpu,
        "hw": hw,
        "perf": perf,
        "delay": delay,
        "mean_on": mean_on,
    }

    # age offline jobs
    new_rem = jnp.where(off_active, st.off_remaining - 1, st.off_remaining)
    st = st.replace(off_remaining=new_rem,
                    off_active=off_active & (new_rem > 0))
    return st, out


def _window_core(state: ClusterState, profiles, fleet, t0, key,
                 num_ticks: int):
    """Scan num_ticks ticks. Returns (new_state, accumulated telemetry)."""

    def tick(st, inp):
        t, k = inp
        return _tick(st, profiles, fleet, t, k)

    keys = jax.random.split(key, num_ticks)
    ts = t0 + jnp.arange(num_ticks, dtype=jnp.float32)
    state, outs = jax.lax.scan(tick, state, (ts, keys))

    summary = {
        "hist_on": outs["hist_on"].sum(0),          # (N, S_ON, 200)
        "hist_off": outs["hist_off"].sum(0),        # (N, S_OFF, 200)
        "rt": outs["rt"],                           # (W, N, S_ON)
        "qps": outs["qps"].mean(0),                 # (N, S_ON)
        "cpu_util": outs["cpu_util"].mean(0),       # (N,)
        "mem_util": outs["mem_util"].mean(0),
        "mem_used": outs["mem_used"].mean(0),
        "cpu_demand": outs["cpu_demand"].mean(0),
        "hw": outs["hw"].mean(0),                   # (N, 8)
        "perf": outs["perf"].mean(0),               # (N, 12)
        "delay": outs["delay"].mean(0),             # (N,)
        "mean_on": outs["mean_on"].mean(0),         # (N, S_ON)
        "cpu_util_series": outs["cpu_util"],        # (W, N)
        "mem_util_series": outs["mem_util"],
    }
    return state, summary



def _rollout_chunks_impl(state: ClusterState, profiles, fleet, t0, keys):
    """Scan CHUNK-tick chunks under one dispatch; ``keys`` is (chunks, 2).

    Returns (final_state, stacked per-chunk summaries).  Each chunk is one
    ``_window_core`` call with its own key, so merging the stacked
    summaries host-side (``merge_summaries``) reproduces an eager loop of
    per-chunk ``_window_core`` calls bit-for-bit.

    The incoming ``state`` is donated: XLA writes the final state back into
    the input buffers, so the dispatch never holds two full copies of the
    per-node arrays.  Callers must not reuse the passed-in state (the
    ``Cluster`` shell reassigns ``self.state`` from the result).
    """

    def body(carry, k):
        st, t = carry
        st, summary = _window_core(st, profiles, fleet, t, k, CHUNK)
        return (st, t + CHUNK), summary

    (state, _), stacked = jax.lax.scan(body, (state, jnp.float32(t0)), keys)
    return state, stacked


rollout_chunks = jax.jit(_rollout_chunks_impl, donate_argnums=(0,))


@partial(jax.jit, static_argnames=("num_chunks",))
def chunk_key_stream(key, num_chunks: int):
    """The per-chunk keys of ``Cluster.rollout``: iterative splits of its key.

    Returns (advanced_key, (num_chunks, 2) stacked chunk keys): a
    ``lax.scan`` of ``key, k = jax.random.split(key)``, one compiled
    program per chunk count, bitwise the eager split loop.  The stream is
    prefix-stable: the first k keys for a given seed never change as more
    chunks are requested, which is what lets a batched replay reuse the
    reference run's exact randomness.
    """
    def body(k, _):
        k, sub = jax.random.split(k)
        return k, sub

    return jax.lax.scan(body, key, None, length=num_chunks)


def seed_keys(sim_seeds) -> jax.Array:
    """(B, 2) keys, row i bitwise ``jax.random.PRNGKey(int(sim_seeds[i]))``,
    from one program.  The seeds go over as int64, as ``PRNGKey`` converts
    a Python int, so seeds of 2**31 and above key the same way."""
    seeds = np.asarray([int(s) for s in sim_seeds], np.int64)
    return _seed_keys(jnp.asarray(seeds))


_seed_keys = jax.jit(jax.vmap(jax.random.PRNGKey))


@partial(jax.jit, static_argnames=("num_windows", "cpw"))
def replay_key_stream(keys, num_windows: int, cpw: int):
    """(B, num_windows, cpw, 2) replay chunk keys from (B, 2) seed keys:
    each row's ``chunk_key_stream`` of ``num_windows * cpw`` chunks, every
    seed in one program, window by window."""
    ks = jax.vmap(lambda k: chunk_key_stream(k, num_windows * cpw)[1])(keys)
    return ks.reshape(keys.shape[0], num_windows, cpw, ks.shape[-1])


def merge_summaries(parts: list[dict]):
    """The host-side chunk merge: histograms sum, series concatenate,
    everything else is the mean of per-chunk means.  Works on np or jnp
    leaves (IEEE adds in the same order, so both give identical bits)."""
    if len(parts) == 1:
        return parts[0]
    xp = np if isinstance(next(iter(parts[0].values())), np.ndarray) else jnp
    merged = {}
    for k in parts[0]:
        vals = [p[k] for p in parts]
        if k in ("hist_on", "hist_off"):
            merged[k] = sum(vals[1:], vals[0])
        elif k in ("rt", "cpu_util_series", "mem_util_series"):
            merged[k] = xp.concatenate(vals, axis=0)
        else:
            merged[k] = sum(vals[1:], vals[0]) / len(vals)
    return merged


# --------------------------------------------------------------------------
# scan-over-windows with the detector/forecaster folded into the carry
# --------------------------------------------------------------------------


def fold_configs(det_cfg=None, fc_cfg=None) -> tuple[dict, dict]:
    """Scalar bundles for the folded detector node track and forecaster
    moment update (defaults match the host-side DetectorConfig /
    ForecastConfig, so the in-scan fold is the same math)."""
    from repro.control.detector import DetectorConfig
    from repro.control.forecast import ForecastConfig

    d = det_cfg or DetectorConfig()
    f = fc_cfg or ForecastConfig()
    det = dict(decay=d.decay, alpha=d.baseline_alpha, slack=d.slack,
               drift_thr=d.drift_threshold, q=d.quantile,
               abs_thr=d.abs_threshold, warmup=d.warmup)
    fc = dict(decay=f.decay, ridge=f.ridge, alpha=f.err_alpha,
              qps_floor=f.qps_floor)
    return det, fc


def init_fold_state(num_nodes: int):
    """Zeroed carry for the folded detector node track + forecaster moments."""
    from repro.control.forecast import NUM_FEATURES

    return (
        jnp.zeros((num_nodes, metric.NUM_BINS), jnp.float32),   # det hist
        jnp.zeros((num_nodes,), jnp.float32),                   # det mu
        jnp.zeros((num_nodes,), jnp.float32),                   # det cusum
        jnp.int32(0),                                           # det steps
        jnp.zeros((num_nodes, S_ON, NUM_FEATURES, NUM_FEATURES),
                  jnp.float32),                                 # fc A
        jnp.zeros((num_nodes, S_ON, NUM_FEATURES), jnp.float32),  # fc b
        jnp.zeros((num_nodes, S_ON), jnp.float32),              # fc err
        jnp.zeros((num_nodes, S_ON), jnp.int32),                # fc count
    )


def _scan_windows_impl(state, profiles, fleet, t0, keys, events, det, fc,
                       fold0, num_windows):
    """One full experiment timeline inside jit: scan telemetry windows, each
    window = (apply that chunk's events -> CHUNK-tick rollout) per chunk,
    then fold the window's node histograms into the detector's CUSUM track
    and its window-mean QPS into the forecaster's harmonic moments.

    keys (W, C, 2), events leaves (W, C, E, ...).  Outputs are lite:
    per-window RT series, window-mean qps/cpu/mem and hotspot flags.
    Only the first ``num_windows`` (a traced int32 scalar) windows run; a
    window past them passes the carry through and emits zeros (``hot``
    False), so one executable serves every real length of a size class.
    The predicate is unbatched under the seed vmap, so it stays a real
    conditional and the skipped windows cost no ticks.
    """
    from repro.control.detector import node_track_step
    from repro.control.forecast import _forecast_update

    def run(carry, wkeys, ev):
        st, t, dh, dmu, dcu, dsteps, A, b, err, cnt = carry

        def chunk(cc, cxs):
            st, t = cc
            ck, cev = cxs
            st = apply_events(st, cev)
            st, summ = _window_core(st, profiles, fleet, t, ck, CHUNK)
            lite = {
                "rt": summ["rt"],
                "qps": summ["qps"],
                "cpu_util": summ["cpu_util"],
                "mem_util": summ["mem_util"],
                "node_hist": summ["hist_on"].sum(1) + summ["hist_off"].sum(1),
            }
            return (st, t + CHUNK), lite

        (st, t), cs = jax.lax.scan(chunk, (st, t), (wkeys, ev))
        rt = cs["rt"].reshape((-1,) + cs["rt"].shape[2:])  # (C*CHUNK, N, S_ON)
        node_hist = cs["node_hist"].sum(0)                 # (N, 200)
        qps = cs["qps"].mean(0)                            # (N, S_ON)

        dh, _avg, _pt, dmu, dcu, _trip, _dt, _at, _raw, hot = node_track_step(
            dh, dmu, dcu, dsteps, node_hist, det["decay"], det["alpha"],
            det["slack"], det["drift_thr"], det["q"], det["abs_thr"],
            det["warmup"])
        dsteps = dsteps + 1
        A, b, err, cnt, _pred = _forecast_update(
            A, b, err, cnt, t, qps, st.on_active, fc["decay"], fc["ridge"],
            fc["alpha"], fc["qps_floor"])

        out = {
            "rt": rt,
            "qps": qps,
            "cpu_util": cs["cpu_util"].mean(0),
            "mem_util": cs["mem_util"].mean(0),
            "hot": hot,
        }
        return (st, t, dh, dmu, dcu, dsteps, A, b, err, cnt), out

    def skip(carry, wkeys, ev):
        out = jax.eval_shape(run, carry, wkeys, ev)[1]
        return carry, jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), out)

    def window(carry, xs):
        w, wkeys, ev = xs
        return jax.lax.cond(w < num_windows, run, skip, carry, wkeys, ev)

    carry0 = (state, jnp.float32(t0)) + fold0
    ws = jnp.arange(keys.shape[0], dtype=jnp.int32)
    carry, outs = jax.lax.scan(window, carry0, (ws, keys, events))
    st, t, dh, dmu, dcu, dsteps, A, b, err, cnt = carry
    final = {"state": st, "t": t, "det_hist": dh, "det_mu": dmu,
             "det_cusum": dcu, "fc_A": A, "fc_b": b, "fc_err": err,
             "fc_count": cnt}
    return final, outs


# One jitted executable per engine configuration: (stacked state?, device
# set).  vmap over a leading seed axis of `keys`; the state/plan are shared
# (common-random-placements replay) or themselves stacked per seed; the
# fleet is hardware, so it is always shared across seeds.
_ENGINE_CACHE: dict = {}


def _batched_fn(stacked: bool, mesh=None):
    """Build (and memoize) the batched rollout executable.

    ``mesh=None`` is the single-device vmap; with a 1-D "seeds" mesh the
    identical vmapped scan is wrapped in ``shard_map`` so each host device
    runs its own shard of the batch — seeds never communicate, so the
    per-seed results are bitwise those of the vmap path (check_vma=False:
    the replicated inputs are read-only, nothing needs cross-device
    verification).  The stacked state is donated (each seed's carry dies
    into its own final state); the shared state cannot be (a broadcast
    input buffer is smaller than any batched output, so XLA could not
    reuse it anyway).
    """
    cache_key = (stacked,
                 None if mesh is None
                 else tuple(d.id for d in mesh.devices.flat))
    fn = _ENGINE_CACHE.get(cache_key)
    if fn is not None:
        return fn
    batched = jax.vmap(
        _scan_windows_impl,
        in_axes=((0 if stacked else None), None, None, None, 0, None, None,
                 None, None, None))
    if mesh is not None:
        from jax.sharding import PartitionSpec

        seeds, rep = PartitionSpec("seeds"), PartitionSpec()
        batched = jax.shard_map(
            batched, mesh=mesh,
            in_specs=((seeds if stacked else rep), rep, rep, rep, seeds,
                      rep, rep, rep, rep, rep),
            out_specs=seeds, check_vma=False)
    fn = jax.jit(batched, donate_argnums=(0,) if stacked else ())
    _ENGINE_CACHE[cache_key] = fn
    return fn


def batched_rollout(state: ClusterState, profiles, t0, keys, events,
                    det_cfg=None, fc_cfg=None, fleet: FleetParams = None,
                    devices: int = None, num_windows: int = None):
    """Evaluate one placement/action plan under many simulation seeds.

    state: a single ClusterState (shared across seeds) or a stacked pytree
        with a leading batch axis matching ``keys``.  A stacked state is
        DONATED — do not reuse it after the call.
    keys: (B, W, C, 2) per-seed chunk keys (see ``chunk_key_stream``).
    events: ``extract_plan`` output, shared across the batch.
    fleet: per-node delay-curve parameters, shared across the batch;
        ``None`` means the homogeneous ``FleetParams.uniform`` fleet.
    devices: shard the seed axis across this many devices via
        ``shard_map``.  More devices than the runtime exposes raises
        (``launch.mesh.make_seed_mesh``); on CPU, launch with
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to get N.
        The batch is padded to a device multiple by repeating the last
        seed and the padding is sliced off before returning, so results
        are bitwise the single-device vmap results.
    num_windows: how many leading windows of ``keys``/``events`` are real
        (``None``: all W).  It is traced data, so plans of one size class
        share one executable whatever their real length.  The windows past
        it run no tick: their outputs are zeros (``hot`` False), and
        ``final`` is the carry after the last real window, as an unpadded
        plan's is.

    Returns (final, outs) with a leading B axis on every leaf: ``outs`` has
    per-window RT series (B, W, C*CHUNK, N, S_ON), window-mean qps/cpu/mem,
    and the folded detector's hotspot flags (B, W, N).
    """
    det, fc = fold_configs(det_cfg, fc_cfg)
    batched_state = state.cpu_sum.ndim == 2
    num_nodes = state.cpu_sum.shape[-1]
    if fleet is None:
        fleet = FleetParams.uniform(num_nodes)
    fold0 = init_fold_state(num_nodes)

    mesh, batch = None, keys.shape[0]
    if devices is not None and devices > 1:
        from repro.launch.mesh import make_seed_mesh

        mesh = make_seed_mesh(devices)
        pad = (-batch) % devices
        if pad:
            idx = np.concatenate([np.arange(batch), np.full(pad, batch - 1)])
            keys = keys[idx]
            if batched_state:
                state = jax.tree_util.tree_map(lambda x: x[idx], state)

    if num_windows is None:
        num_windows = keys.shape[1]
    fn = _batched_fn(batched_state, mesh)
    final, outs = fn(state, profiles, fleet, jnp.float32(t0), keys, events,
                     det, fc, fold0, np.int32(num_windows))
    if keys.shape[0] != batch:
        final = jax.tree_util.tree_map(lambda x: x[:batch], final)
        outs = jax.tree_util.tree_map(lambda x: x[:batch], outs)
    return final, outs
