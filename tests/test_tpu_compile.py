"""Compile-only checks of the main path's device programs for a TPU v5e.

The TPU compiler is installed with JAX, and it compiles for a chip that is
described rather than attached.  These tests compile the orchestrator's
hot programs at real widths for one chip of a described ``v5e:2x2``
topology: the fused tick kernel, the scanned rollout window and the
admission top-k prefilter.  They catch what interpret mode cannot (Mosaic
layout limits, VMEM budgets, unsupported lowerings) without a chip.
Nothing runs, so they say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every pytest-xdist worker
imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.cluster import state as cstate
from repro.cluster import workloads as W
from repro.cluster.fleet import topk_candidates
from repro.kernels.rollout_tick import fused_tick

NODES = 1000          # the smoke fleet: make_fleet(1000, DEFAULT_MIX)
ADMIT_NODES = 5000    # the largest admission view bench_scheduler_latency uses
SLOTS = cstate.S_ON + cstate.S_OFF


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype,
                                       sharding=sharding), tree)


def test_fused_tick_compiles_for_v5e(one_chip):
    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    k = cstate.SAMPLES_PER_TICK
    compiled = fused_tick.lower(
        f32(NODES, 8), f32(NODES, SLOTS), f32(NODES, SLOTS),
        f32(NODES, SLOTS * k), f32(NODES, SLOTS * k),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rollout_chunks_compiles_for_v5e(one_chip):
    """One 40-tick window (4 chunks) of the jnp tick at 1k nodes."""
    state = _shapes(cstate.ClusterState.create(NODES), one_chip)
    fleet = _shapes(cstate.FleetParams.uniform(NODES), one_chip)
    profiles = _shapes(W.online_arrays(), one_chip)
    keys = jax.ShapeDtypeStruct((4, 2), jnp.uint32, sharding=one_chip)
    t0 = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = cstate.rollout_chunks.lower(state, profiles, fleet, t0,
                                           keys).compile()
    assert compiled.memory_analysis() is not None


def test_topk_candidates_compiles_for_v5e(one_chip):
    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    node = f32(ADMIT_NODES)
    compiled = topk_candidates.lower(node, node, node, node, f32(), f32(),
                                     f32(), f32(), k=64).compile()
    idx, vals = compiled.out_info
    assert idx.shape == (64,) and vals.shape == (64,)
