"""Pallas TPU kernel for the RWKV-6 WKV recurrence (chunked).

Grid: (batch*heads, num_chunks); the chunk axis is sequential
("arbitrary" dimension semantics on TPU) so the (P x P) state matrix
stays resident in a VMEM scratch buffer across chunk iterations — the
TPU-native adaptation of RWKV's CUDA kernel (which keeps per-block state
in registers/shared memory).

Per chunk: cumulative per-channel log-decay in VREGs, inter-chunk term
via one (Lc,P)@(P,P) MXU contraction, intra-chunk pairwise term via a
strictly-lower-masked (Lc,Lc) matmul, then a rank-Lc state update.

VMEM per program: state P*P*4 + ~5 chunk tiles Lc*P*4
= 64*64*4 + 5*64*64*4 ~= 100 KB << 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import resolve_interpret


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_ref):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)     # (Lc, P)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)     # (1, P)
    S = state_ref[...]                   # (P, P)

    logw = jnp.log(jnp.maximum(w, 1e-38))
    cum = jnp.cumsum(logw, axis=0)       # inclusive
    A_incl = jnp.exp(cum)
    A_excl = jnp.exp(cum - logw)
    total = jnp.exp(cum[-1:, :])         # (1, P)

    qd = r * A_excl
    y_inter = jax.lax.dot_general(qd, S, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    kd = k / jnp.maximum(A_incl, 1e-30)
    att = jax.lax.dot_general(qd, kd, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    Lc = r.shape[0]
    ti = jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 1)
    att = jnp.where(ti > si, att, 0.0)
    diag = (r * (u * k)).sum(axis=1)
    y_intra = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32) \
        + diag[:, None] * v
    o_ref[0] = (y_inter + y_intra).astype(o_ref.dtype)

    kw = k * (total / jnp.maximum(A_incl, 1e-30))
    state_ref[...] = S * total.T + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("num_heads", "chunk", "interpret"))
def wkv_pallas(r, k, v, w, u, num_heads: int, chunk: int = 64,
               interpret: bool | None = None):
    """r/k/v/w: (B, T, H*P); u: (H, P). Returns y (B, T, H*P)."""
    B, T, HP = r.shape
    H = num_heads
    P = HP // H
    chunk = min(chunk, T)
    assert T % chunk == 0
    nc = T // chunk

    def prep(x):
        return x.reshape(B, T, H, P).transpose(0, 2, 1, 3).reshape(B * H, T, P)

    rt, kt, vt, wt = map(prep, (r, k, v, w))
    ut = jnp.broadcast_to(u[None], (B, H, P)).reshape(B * H, 1, P)

    out = pl.pallas_call(
        _wkv_kernel,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, P), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T, P), r.dtype),
        scratch_shapes=[pltpu.VMEM((P, P), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(rt, kt, vt, wt, ut)
    return out.reshape(B, H, T, P).transpose(0, 2, 1, 3).reshape(B, T, HP)
