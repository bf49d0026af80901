"""Pallas TPU flash-attention kernel (forward).

Grid: (batch*heads, num_q_blocks).  Each program streams KV blocks for one
(128 x head_dim) query tile held in VMEM, maintaining the online-softmax
accumulator in f32 VREGs.  Causal masking and sliding windows are applied
per KV tile; with causal=True the KV stream stops at the query block's
frontier via a masked loop bound (grid is static, masked tiles are skipped
by zeroing their contribution — the MXU work is still saved on TPU because
the loop bound itself is dynamic).

MXU alignment: q_block=128 rows (8x128-lane registers), head_dim padded to
a multiple of 128 by the wrapper when necessary.  VMEM footprint per
program: q tile + 2 kv tiles + accumulator ~= (128 + 2*kv_block) * hd * 4B
(< 1 MB at kv_block=256, hd=128), far under the ~16 MB budget.

Validated on CPU in interpret mode against ref.py (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mode import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, kv_block: int, causal: bool,
                  sliding_window: int, seq_len: int, q_block: int):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)          # (q_block, hd)
    hd = q.shape[-1]
    scale = 1.0 / math.sqrt(hd)

    n_kv = seq_len // kv_block
    q_start = qi * q_block

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[pl.ds(j * kv_block, kv_block), :]
        v = v_ref[pl.ds(j * kv_block, kv_block), :]
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                 # (q_block, kv_block)
        q_idx = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_idx = j * kv_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones(s.shape, jnp.bool_)
        if causal:
            mask = mask & (q_idx >= k_idx)
        if sliding_window > 0:
            mask = mask & (k_idx > q_idx - sliding_window - 1)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        pv = jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha[:, None] + pv
        return acc_new, m_new, l_new

    # causal: only stream KV blocks up to this q block's frontier
    upper = n_kv if not causal else (q_start + q_block + kv_block - 1) // kv_block
    upper = min(upper, n_kv) if isinstance(upper, int) else upper
    acc0 = jnp.zeros((q.shape[0], hd), jnp.float32)
    m0 = jnp.full((q.shape[0],), NEG_INF, jnp.float32)
    l0 = jnp.zeros((q.shape[0],), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, upper, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sliding_window", "q_block", "kv_block", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,           # (B, S, H, hd) — H == KV heads (pre-repeated)
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: int = 0,
    q_block: int = 128,
    kv_block: int = 256,
    interpret: bool | None = None,
):
    B, S, H, hd = q.shape
    q_block = min(q_block, S)
    kv_block = min(kv_block, S)
    assert S % q_block == 0 and S % kv_block == 0

    # (B, S, H, hd) -> (B*H, S, hd) program-per-head layout
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)

    kernel = functools.partial(
        _flash_kernel,
        kv_block=kv_block,
        causal=causal,
        sliding_window=sliding_window,
        seq_len=S,
        q_block=q_block,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * H, S // q_block),
        in_specs=[
            pl.BlockSpec((None, q_block, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, S, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, S, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, q_block, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(qt, kt, vt)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
