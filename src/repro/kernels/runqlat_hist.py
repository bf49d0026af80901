"""Pallas TPU kernel for the paper's metric hot path: binning scheduling-
latency samples into the 200x5 runqlat histogram, vectorized over services.

The eBPF original updates a per-CPU hash map; the TPU-native adaptation is
a one-hot matmul: each (samples_block x 200) one-hot tile is accumulated
into the service's histogram via the MXU (one-hot contraction against a
ones vector == histogram), with the 200-bin histogram resident in VMEM
scratch across sample blocks.

Grid: (num_series, num_sample_blocks); block = 512 samples.
VMEM per program: one-hot tile 512*200*4 + hist 200*4 ~= 410 KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.metric import NUM_BINS, BIN_WIDTH
from repro.kernels.mode import resolve_interpret


def _hist_kernel(samples_ref, weights_ref, o_ref, acc_ref):
    bi = pl.program_id(1)
    nblocks = pl.num_programs(1)

    @pl.when(bi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = samples_ref[0].astype(jnp.float32)        # (block,)
    wgt = weights_ref[0].astype(jnp.float32)      # (block,)
    idx = jnp.clip(jnp.floor(s / BIN_WIDTH), 0, NUM_BINS - 1).astype(jnp.int32)
    onehot = (idx[:, None] ==
              jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], NUM_BINS), 1))
    onehot = onehot.astype(jnp.float32) * wgt[:, None]
    # histogram = ones @ onehot  (MXU-friendly reduction over samples)
    acc_ref[...] = acc_ref[...] + onehot.sum(axis=0, keepdims=True)

    @pl.when(bi == nblocks - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def runqlat_hist_pallas(samples, weights=None, block: int = 512,
                        interpret: bool | None = None):
    """samples: (S_series, N) latencies -> (S_series, 200) histograms."""
    S, N = samples.shape
    if weights is None:
        weights = jnp.ones((S, N), jnp.float32)
    block = min(block, N)
    pad = (-N) % block
    if pad:
        samples = jnp.pad(samples, ((0, 0), (0, pad)))
        weights = jnp.pad(weights, ((0, 0), (0, pad)))
    nb = samples.shape[1] // block

    out = pl.pallas_call(
        _hist_kernel,
        grid=(S, nb),
        in_specs=[
            pl.BlockSpec((1, block), lambda s, b: (s, b)),
            pl.BlockSpec((1, block), lambda s, b: (s, b)),
        ],
        out_specs=pl.BlockSpec((1, NUM_BINS), lambda s, b: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((S, NUM_BINS), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, NUM_BINS), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(samples, weights)
    return out
