"""Pallas TPU kernel for the Mamba-2 SSD chunked scan (Zamba2 backbone).

Grid: (batch*heads, num_chunks), sequential chunk axis; the (P x N) SSM
state lives in a VMEM scratch across chunks.  Scalar-per-head decay makes
the intra-chunk pairwise decay matrix exactly representable: dmat[t,s] =
exp(cum[t]-cum[s]) masked to the lower triangle (all ratios <= 1: stable).

VMEM per program: state P*N*4 + chunk tiles (x: Lc*P, B/C: Lc*N, dt: Lc)
~= 64*64*4 + (64*64 + 2*64*64 + 64)*4 ~= 82 KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import resolve_interpret


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, state_ref):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)      # (Lc, P)
    dt = dt_ref[0].astype(jnp.float32)    # (Lc, 1)
    A = a_ref[0].astype(jnp.float32)      # (1, 1)
    Bc = b_ref[0].astype(jnp.float32)     # (Lc, N)
    Cc = c_ref[0].astype(jnp.float32)     # (Lc, N)
    S = state_ref[...]                    # (P, N)

    loga = dt * A                         # (Lc, 1), <= 0
    cum = jnp.cumsum(loga, axis=0)        # (Lc, 1) inclusive
    tot = jnp.exp(cum[-1:, :])            # (1, 1)

    # inter-chunk: y_inter[t] = exp(cum[t]) * (S C_t)
    SC = jax.lax.dot_general(Cc, S, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Lc, P)
    y_inter = jnp.exp(cum) * SC

    # intra-chunk
    Lc = x.shape[0]
    dmat = jnp.exp(cum - cum[:, 0][None, :])          # (Lc, Lc) = cum[t]-cum[s]
    ti = jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 1)
    dmat = jnp.where(ti >= si, dmat, 0.0)
    bc = jax.lax.dot_general(Cc, Bc, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Lc, Lc)
    wmat = dmat * bc * dt[:, 0][None, :]              # (t, s)
    y_intra = jax.lax.dot_general(wmat, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    o_ref[0] = (y_inter + y_intra).astype(o_ref.dtype)

    # state update
    decay_s = jnp.exp(cum[-1:, :] - cum) * dt         # (Lc, 1)
    xw = x * decay_s                                  # (Lc, P)
    state_ref[...] = S * tot + jax.lax.dot_general(
        xw, Bc, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(x, dt, A, B_, C, chunk: int = 64,
               interpret: bool | None = None):
    """x: (B,T,H,P), dt: (B,T,H), A: (H,), B_/C: (B,T,N). Returns y like x."""
    Bsz, T, H, P = x.shape
    N = B_.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0
    nc = T // chunk

    xt = x.transpose(0, 2, 1, 3).reshape(Bsz * H, T, P)
    dtt = dt.transpose(0, 2, 1).reshape(Bsz * H, T, 1)
    at = jnp.broadcast_to(A[None, :], (Bsz, H)).reshape(Bsz * H, 1, 1)
    bt = jnp.broadcast_to(B_[:, None], (Bsz, H, T, N)).reshape(Bsz * H, T, N)
    ct = jnp.broadcast_to(C[:, None], (Bsz, H, T, N)).reshape(Bsz * H, T, N)

    out = pl.pallas_call(
        _ssd_kernel,
        grid=(Bsz * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz * H, T, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xt, dtt, at, bt, ct)
    return out.reshape(Bsz, H, T, P).transpose(0, 2, 1, 3)
