"""Compile-only checks of the main path's device programs for a TPU v5e.

The TPU compiler is installed with JAX, and it compiles for a chip that is
described rather than attached.  These tests compile the orchestrator's
hot programs at real widths for one chip of a described ``v5e:2x2``
topology: the seed-batched replay engine, the scanned rollout window and
the admission top-k prefilter.  They catch what interpret mode cannot (Mosaic
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

NODES = 1000          # the smoke fleet: make_fleet(1000, DEFAULT_MIX)
ADMIT_NODES = 5000    # the largest admission view bench_scheduler_latency uses
# the paper12.replay cell: 20 seeds a call, 12 nodes, 3 days of 40-tick
# windows bucketed to 256, 4 chunks a window, and its plan's bucketed
# events per chunk
REPLAY_SEEDS, REPLAY_NODES, REPLAY_WINDOWS, REPLAY_CPW, REPLAY_EVENTS = (
    20, 12, 256, 4, 8)


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
    # a Python scalar is traced weakly typed, as the call itself traces it
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            jnp.shape(x), jnp.asarray(x).dtype, sharding=sharding,
            weak_type=isinstance(x, (int, float))), tree)


def test_replay_engine_compiles_for_v5e(one_chip):
    """``batched_rollout``'s shared-state engine at ``paper12.replay``'s
    shapes: the program that cell replays its plan with."""
    # every event of the plan in its first chunk: the events-per-chunk
    # dimension is then the cell's bucketed width
    log = [("place_on", 0.0, n % REPLAY_NODES, n // REPLAY_NODES, 0, 300.0,
            0.4) for n in range(REPLAY_EVENTS)]
    events = cstate.extract_plan(log, 0.0, REPLAY_WINDOWS, REPLAY_CPW,
                                 bucket=True)
    assert events["op"].shape == (REPLAY_WINDOWS, REPLAY_CPW, REPLAY_EVENTS)
    det, fc = cstate.fold_configs()
    args = (cstate.ClusterState.create(REPLAY_NODES), W.online_arrays(),
            cstate.FleetParams.uniform(REPLAY_NODES), jnp.float32(0.0))
    args = _shapes(args, one_chip) + (
        jax.ShapeDtypeStruct(
            (REPLAY_SEEDS, REPLAY_WINDOWS, REPLAY_CPW, 2), jnp.uint32,
            sharding=one_chip),) + _shapes(
        (events, det, fc, cstate.init_fold_state(REPLAY_NODES)), one_chip)
    compiled = cstate._batched_fn(stacked=False).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    _, outs = compiled.out_info
    assert outs["rt"].shape == (REPLAY_SEEDS, REPLAY_WINDOWS,
                                REPLAY_CPW * cstate.CHUNK, REPLAY_NODES,
                                cstate.S_ON)


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
