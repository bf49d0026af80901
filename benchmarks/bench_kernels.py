"""Pallas kernel timings (interpret mode on CPU — correctness-bearing, not
TPU-speed-bearing) vs their pure-jnp oracles, plus the model-layer flash
attention. `derived` carries max|err| vs the oracle."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.models.attention import attention

KEY = jax.random.PRNGKey(0)


def _time(fn, *args, reps=3):
    fn(*args)  # compile/warm
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, (time.time() - t0) / reps * 1e6


def run(fast: bool = True):
    out = []
    B, S, H, hd = 1, 256, 2, 64
    q = jax.random.normal(jax.random.fold_in(KEY, 0), (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, H, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, H, hd))

    o_p, us_p = _time(lambda *a: ops.flash_attention(*a, q_block=128, kv_block=128), q, k, v)
    o_r, us_r = _time(lambda *a: ref.flash_attention_ref(*a), q, k, v)
    err = float(jnp.abs(o_p - o_r).max())
    out.append(("kernels.flash_attention.pallas_interp", us_p, f"err={err:.2e}"))
    out.append(("kernels.flash_attention.ref", us_r, "oracle"))
    o_j, us_j = _time(lambda *a: attention(*a, causal=True, kv_block=128), q, k, v)
    out.append(("kernels.flash_attention.jnp_model_path", us_j,
                f"err={float(jnp.abs(o_j - o_r).max()):.2e}"))

    P = 32
    r_ = jax.random.normal(jax.random.fold_in(KEY, 11), (B, S, H * P))
    k_ = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, H * P))
    v_ = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, H * P))
    w_ = jax.random.uniform(jax.random.fold_in(KEY, 5), (B, S, H * P), minval=0.9, maxval=0.999)
    u_ = jax.random.normal(jax.random.fold_in(KEY, 6), (H, P)) * 0.1
    o_p, us_p = _time(lambda *a: ops.wkv(*a, H), r_, k_, v_, w_, u_)
    o_r, us_r = _time(lambda *a: ref.wkv_ref(*a, H), r_, k_, v_, w_, u_)
    out.append(("kernels.rwkv_wkv.pallas_interp", us_p,
                f"err={float(jnp.abs(o_p - o_r).max()):.2e}"))
    out.append(("kernels.rwkv_wkv.ref", us_r, "oracle"))

    N = 16
    x = jax.random.normal(jax.random.fold_in(KEY, 12), (B, S, H, P))
    dt = jax.random.uniform(jax.random.fold_in(KEY, 7), (B, S, H), minval=0.01, maxval=0.2)
    A = -jax.random.uniform(jax.random.fold_in(KEY, 8), (H,), minval=0.5, maxval=2.0)
    Bm = jax.random.normal(jax.random.fold_in(KEY, 9), (B, S, N))
    Cm = jax.random.normal(jax.random.fold_in(KEY, 10), (B, S, N))
    o_p, us_p = _time(ops.ssd, x, dt, A, Bm, Cm)
    o_r, us_r = _time(ref.ssd_ref, x, dt, A, Bm, Cm)
    out.append(("kernels.mamba2_ssd.pallas_interp", us_p,
                f"err={float(jnp.abs(o_p - o_r).max()):.2e}"))
    out.append(("kernels.mamba2_ssd.ref", us_r, "oracle"))

    s = jax.random.uniform(jax.random.fold_in(KEY, 13), (8, 4096), minval=0, maxval=1100)
    o_p, us_p = _time(ops.runqlat_hist, s)
    o_r, us_r = _time(ref.runqlat_hist_ref, s)
    out.append(("kernels.runqlat_hist.pallas_interp", us_p,
                f"err={float(jnp.abs(o_p - o_r).max()):.2e};"
                f"samples_per_s={8 * 4096 / (us_p / 1e6):.3g}"))
    out.append(("kernels.runqlat_hist.ref", us_r, "oracle"))
    return out


if __name__ == "__main__":
    from repro.launch.cache import enable_persistent_cache

    enable_persistent_cache()
    for row in run():
        print(",".join(map(str, row)))
