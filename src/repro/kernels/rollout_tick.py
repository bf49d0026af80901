"""Fused Pallas TPU kernel for the rollout tick's sampling hot loop.

One tick of the simulator spends its budget in three back-to-back stages:
the per-node M/G/1-PS delay curve, the Erlang(2) runqlat draw (two
uniforms and a log per sample), and binning those samples into the 200x5
node histogram.  The jnp path materializes the (N, slots, 16) sample and
(N, slots, 16, 200) one-hot intermediates in HBM between stages; this
kernel fuses all three into a single VMEM pass per node block, reusing the
MXU one-hot-contraction idiom from ``kernels.runqlat_hist`` (histogram ==
weights-vector @ one-hot matrix).

Inputs are pre-packed by ``cluster.state._tick_pallas`` (which draws the
exact random stream of the jnp reference tick):

* ``nodev`` (N, 8) — [rho_p, threads_total, cores, delay_base,
  delay_scale, rho_knee, oversub_slope, delay_noise] per node
* ``jit_all`` (N, S) — per-slot pod jitter, online slots first
* ``act_all`` (N, S) — slot-active mask as f32
* ``u1``/``u2`` (N, S*K) — Erlang(2) uniforms, K samples per slot

The wrapper repeats ``jit_all``/``act_all`` K times along the lane axis
before the call, so the kernel body is 2-D elementwise work plus one
batched contraction, with no reshape Mosaic would have to lower.

The kernel writes the node histogram (N, 200) and node delay (N, 1); the
wrapper derives the per-slot runqlat mean (N, S) from the delay in XLA.
``fused_tick_reference`` is the same math in plain jnp — the unit-parity
oracle for interpret mode on CPU.  Whether the kernel beats the jnp tick
on the chip has not been measured.

Grid: (N / block,); VMEM per program ~ block * S*K * 200 * 4 bytes for the
one-hot tile (block=8, S=14, K=16 -> ~1.4 MB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.metric import BIN_WIDTH, NUM_BINS
from repro.kernels.mode import resolve_interpret


def _node_delay(v, xp=jnp):
    """Delay curve + oversubscription + lognormal jitter from a packed
    (block, 8) node vector.  Written as ``rho * rho`` (not ``rho**2``) so
    the lowering matches the jnp tick's ``integer_pow`` bit-for-bit."""
    rho, thr, cores = v[:, 0], v[:, 1], v[:, 2]
    base, scale, knee, slope, noise = (
        v[:, 3], v[:, 4], v[:, 5], v[:, 6], v[:, 7])
    d = base + scale * rho * rho / xp.maximum(1.0 - rho, knee)
    d = d * (1.0 + slope * xp.maximum(thr / cores - 1.0, 0.0))
    return d * xp.exp(0.13 * noise)


def _tick_kernel(nodev_ref, jitx_ref, actx_ref, u1_ref, u2_ref,
                 hist_ref, delay_ref, *, gamma_shape, clip_max):
    d = jnp.clip(_node_delay(nodev_ref[...]), 0.0, clip_max)  # (block,)

    # Erlang(2) == -log(U1 * U2), scaled to the slot mean.  The per-slot
    # jitter and weight arrive already repeated K times along the lanes
    # (block, S*K): Mosaic cannot split a lane axis into (S, K), and the
    # elementwise scale keeps the samples bitwise those of the reference.
    scale = d[:, None] * jnp.maximum(jitx_ref[...], 0.3) / gamma_shape
    samples = -jnp.log(u1_ref[...] * u2_ref[...]) * scale     # (block, S*K)

    block, width = samples.shape
    idx = jnp.clip(jnp.floor(samples / BIN_WIDTH),
                   0, NUM_BINS - 1).astype(jnp.int32)
    onehot = (idx[:, :, None] == jax.lax.broadcasted_iota(
        jnp.int32, (block, width, NUM_BINS), 2)).astype(jnp.float32)
    # node histogram = weights @ one-hot (MXU contraction over samples);
    # 0/1 operands and integer counts are exact at any matmul precision
    hist = jax.lax.dot_general(
        actx_ref[...][:, None, :], onehot, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)

    hist_ref[...] = hist[:, 0, :]
    delay_ref[...] = d[:, None]


@functools.partial(
    jax.jit,
    static_argnames=("gamma_shape", "clip_max", "block", "interpret"))
def fused_tick(nodev, jit_all, act_all, u1, u2, *, gamma_shape: float = 2.0,
               clip_max: float = 2.5 * (NUM_BINS - 1) * BIN_WIDTH,
               block: int = 8, interpret: bool | None = None):
    """Fused delay-curve + Erlang(2) draw + histogram for one tick.

    Returns ``(node_hist (N, 200), delay (N,), mean (N, S))``.
    ``interpret`` follows ``kernels.mode.resolve_interpret``: the Pallas
    interpreter on the CPU backend (what the parity tests exercise), the
    compiled Mosaic kernel on TPU.
    """
    interpret = resolve_interpret(interpret)
    n, slots = jit_all.shape
    k = u1.shape[1] // slots
    block = min(block, n)
    pad = (-n) % block
    if pad:
        # benign rows: rho=0 cores=1 knee=1 -> delay 0; U=1 -> sample 0;
        # act=0 -> zero histogram weight.  Sliced off below.
        padrow = jnp.zeros((pad, nodev.shape[1]), nodev.dtype)
        padrow = padrow.at[:, 2].set(1.0).at[:, 5].set(1.0)
        nodev = jnp.concatenate([nodev, padrow])
        jit_all = jnp.pad(jit_all, ((0, pad), (0, 0)), constant_values=1.0)
        act_all = jnp.pad(act_all, ((0, pad), (0, 0)))
        u1 = jnp.pad(u1, ((0, pad), (0, 0)), constant_values=1.0)
        u2 = jnp.pad(u2, ((0, pad), (0, 0)), constant_values=1.0)
    jitx = jnp.repeat(jit_all, k, axis=1)   # (N, S*K), slot-major like u1/u2
    actx = jnp.repeat(act_all, k, axis=1)

    kernel = functools.partial(
        _tick_kernel, gamma_shape=gamma_shape, clip_max=clip_max)
    npad = nodev.shape[0]

    def rows(width):
        return pl.BlockSpec((block, width), lambda i: (i, 0))

    hist, delay = pl.pallas_call(
        kernel,
        grid=(npad // block,),
        in_specs=[rows(nodev.shape[1]), rows(slots * k), rows(slots * k),
                  rows(slots * k), rows(slots * k)],
        out_specs=[rows(NUM_BINS), rows(1)],
        out_shape=[
            jax.ShapeDtypeStruct((npad, NUM_BINS), jnp.float32),
            jax.ShapeDtypeStruct((npad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(nodev, jitx, actx, u1, u2)
    delay = delay[:n, 0]
    # the slot means feed the RT series, which the rollout stacks per tick;
    # computed here rather than as a kernel output, XLA keeps their layout
    # free (an (N, S) kernel output pins S to the 128-wide lane axis, and
    # the stacked RT series inherits that 16x padding)
    mean = delay[:, None] * jnp.maximum(jit_all[:n], 0.3)
    return hist[:n], delay, mean


def fused_tick_reference(nodev, jit_all, act_all, u1, u2, *,
                         gamma_shape: float = 2.0,
                         clip_max: float = 2.5 * (NUM_BINS - 1) * BIN_WIDTH):
    """Plain-jnp oracle for ``fused_tick`` — same packed inputs, same
    outputs, no Pallas.  Unit tests assert exact agreement in interpret
    mode."""
    n, slots = jit_all.shape
    k = u1.shape[1] // slots
    d = jnp.clip(_node_delay(nodev), 0.0, clip_max)
    mean = d[:, None] * jnp.maximum(jit_all, 0.3)
    g = -jnp.log(u1 * u2)
    samples = g.reshape(n, slots, k) * (mean / gamma_shape)[:, :, None]
    idx = jnp.clip(jnp.floor(samples / BIN_WIDTH),
                   0, NUM_BINS - 1).astype(jnp.int32)
    onehot = (idx[..., None] == jax.lax.broadcasted_iota(
        jnp.int32, (*idx.shape, NUM_BINS), 3)).astype(jnp.float32)
    hist = (onehot * act_all[:, :, None, None]).sum((1, 2))
    return hist, d, mean
