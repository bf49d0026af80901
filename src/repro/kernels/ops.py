"""jit'd public wrappers for the Pallas kernels.

Each wrapper leaves ``interpret`` to ``kernels.mode.resolve_interpret``,
decided while the kernel traces: the Pallas interpreter on the CPU
backend (bit-accurate block/grid semantics, Python speed), the compiled
kernel on TPU.  The model layer switches to these via
ModelConfig.use_pallas.
"""
from __future__ import annotations

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rwkv_wkv import wkv_pallas
from repro.kernels.ssd import ssd_pallas
from repro.kernels.runqlat_hist import runqlat_hist_pallas


def flash_attention(q, k, v, *, causal=True, sliding_window=0,
                    q_block=128, kv_block=256):
    """(B,S,H,hd) x3 -> (B,S,H,hd); equal q/kv head counts (repeat GQA first)."""
    return flash_attention_pallas(
        q, k, v, causal=causal, sliding_window=sliding_window,
        q_block=q_block, kv_block=kv_block,
    )


def wkv(r, k, v, w, u, num_heads, chunk=64):
    return wkv_pallas(r, k, v, w, u, num_heads, chunk=chunk)


def ssd(x, dt, A, B_, C, chunk=64):
    return ssd_pallas(x, dt, A, B_, C, chunk=chunk)


def runqlat_hist(samples, weights=None, block=512):
    return runqlat_hist_pallas(samples, weights, block=block)
