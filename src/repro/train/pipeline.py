"""GPipe-style pipeline parallelism over a `stage` mesh axis.

Layers are stacked (L, ...) with L = num_stages * layers_per_stage and
sharded P("stage") on the stacking dim; microbatches flow through the
stage ring via `lax.ppermute` in the classic skewed schedule
(M + S - 1 ticks for M microbatches over S stages).  Each stage applies
its local layer slice with `lax.scan`.

This is the optional PP feature referenced in DESIGN.md §3: the
production dry-run uses DP(+pod)xTP, but pipeline stages compose with it
by adding a `stage` axis to the mesh.  Correctness (pipeline == sequential
layer application) is asserted on a real multi-device mesh in
tests/test_pipeline.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe_forward(apply_layer, params_stacked, microbatches, *, mesh,
                  stage_axis: str = "stage"):
    """Run microbatches through pipeline stages.

    apply_layer(layer_params, x) -> x   (one layer)
    params_stacked: pytree with leading dim L (sharded over `stage`)
    microbatches: (M, B, ...) activations (replicated across stages)
    Returns (M, B, ...) outputs (replicated).
    """
    n_stage = mesh.shape[stage_axis]

    def stage_body(params_local, mbs):
        s = jax.lax.axis_index(stage_axis)
        M = mbs.shape[0]
        T = M + n_stage - 1  # skewed schedule length

        def apply_stage(x):
            def body(c, lp):
                return apply_layer(lp, c), None
            y, _ = jax.lax.scan(body, x, params_local)
            return y

        perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t while t < M
            inject = mbs[jnp.clip(t, 0, M - 1)]
            cur = jnp.where((s == 0)[..., None] if False else (s == 0),
                            inject, buf)
            active = (t - s >= 0) & (t - s < M)
            y = apply_stage(cur)
            y = jnp.where(active, y, cur)
            # last stage emits microbatch t - (S-1)
            out_idx = jnp.clip(t - (n_stage - 1), 0, M - 1)
            emit = (s == n_stage - 1) & (t >= n_stage - 1)
            outs = jax.lax.dynamic_update_slice(
                outs,
                jnp.where(emit, y, outs[out_idx])[None],
                (out_idx, *([0] * (outs.ndim - 1))),
            )
            nxt = jax.lax.ppermute(y, stage_axis, perm)
            return (nxt, outs), None

        buf0 = jnp.zeros_like(mbs[0])
        outs0 = jnp.zeros_like(mbs)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(T))
        # re-replicate: only the last stage holds real outputs -> psum
        outs = jnp.where(s == n_stage - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, stage_axis)

    return jax.shard_map(
        stage_body,
        mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False,
    )(params_stacked, microbatches)
